"""chip_smoke.py, rehearsed on the CPU platform as a subprocess: the script
the driver runs on the chip must keep its contract — exit 0 and a parseable
last line under --rehearse that never claims a TPU, and a non-zero exit with
no result line when JAX finds no accelerator."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={devices}").strip()
    return subprocess.run(
        [sys.executable, SCRIPT, "--workdir", str(tmp_path / "work"), *args],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=timeout)


def _last_line(proc):
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] != "tpu"
    return last


def test_rehearsal_single_chip(tmp_path):
    proc = _run(["--rehearse", "--sf", "0.01", "--queries", "q1,q3,q5,q18"],
                tmp_path, timeout=580)
    last = _last_line(proc)
    assert last["device"]["count"] == 1
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    phases = [n.get("phase") for n in notes]
    # every query ran cold and hot, the endpoint answered six requests
    assert phases.count("query") == 8 and phases.count("endpoint") == 6
    assert "endpoint.shutdown" in phases and "native" in phases
    # q1 over lineitem cached on the device tier: the run that fills the
    # cache scans, the run that reads it moves no byte at a scan site
    fill, resident = [n for n in notes if n.get("phase") == "resident"]
    assert (fill["run"], resident["run"]) == ("fill", "resident")
    assert fill["h2d_sites"] and resident["h2d_sites"] == {}


def test_rehearsal_four_chips_runs_only_the_mesh_path(tmp_path):
    proc = _run(["--chips", "4", "--rehearse", "--sf", "0.01"], tmp_path,
                timeout=580, devices=4)
    last = _last_line(proc)
    assert last["device"]["count"] == 4
    phases = [json.loads(ln).get("phase") for ln in proc.stdout.splitlines()
              if ln.startswith("{")]
    assert phases.count("mesh") == 2 and "mesh.devices" in phases
    assert "query" not in phases and "endpoint" not in phases


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_no_accelerator_is_a_failure(tmp_path, args):
    """Without --rehearse the CPU platform is refused: non-zero exit, and
    nothing on stdout that could be read as a result."""
    proc = _run(args, tmp_path, timeout=110, devices=4)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
