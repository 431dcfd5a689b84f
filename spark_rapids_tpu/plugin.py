"""Plugin bootstrap — driver/executor lifecycle (SURVEY.md component #1).

Reference: Plugin.scala —
  * RapidsDriverPlugin.init (:154): config fixup (:85-120, injects the SQL
    extension + enforces serializer confs), version check, and the shuffle
    heartbeat manager when the accelerated shuffle is on (:161).
  * RapidsExecutorPlugin.init (:175): cudf version check (:214), explicit
    device + memory initialization (GpuDeviceManager.initializeGpuAndMemory
    :125), heartbeat endpoint registration (:197), semaphore init (:203),
    and CRASH-FAST on failure (:210 System.exit(1)) so the cluster manager
    reschedules the executor rather than running degraded.

Standalone TPU translation: one process hosts both roles. TpuSession
bootstraps the plugin once per process (idempotent, conf from the first
session — matching the reference, where plugin config is process-wide);
`executor_init` performs EXPLICIT device acquisition (ordinal conf,
platform verification, HBM warmup touch that fails fast on a dead or
absent backend) before any query runs, instead of the previous lazy
first-use initialization.
"""

from __future__ import annotations

import threading

from spark_rapids_tpu import config as CFG


class PluginInitError(RuntimeError):
    """Executor init failed — the reference exits the process (Plugin.scala
    :210) so Spark reschedules; standalone callers decide, so we raise."""


_lock = threading.Lock()
_initialized = False
_context: dict = {}


def context() -> dict:
    """The driver plugin context (Plugin.scala:165 plugin-context map):
    holds e.g. the shuffle heartbeat manager for endpoint registration."""
    return _context


def executor_init(conf) -> None:
    """Explicit device acquisition + runtime init (GpuDeviceManager
    .initializeGpuAndMemory analog). Raises PluginInitError on failure."""
    import jax

    from spark_rapids_tpu.runtime.memory import DeviceManager
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore

    try:
        devices = jax.devices()
    except Exception as e:  # backend init failure
        raise PluginInitError(f"no accelerator backend: {e}") from e
    ordinal = conf.get(CFG.DEVICE_ORDINAL)
    if not 0 <= ordinal < len(devices):
        raise PluginInitError(
            f"device ordinal {ordinal} out of range ({len(devices)} visible)")
    # warmup touch: allocate-and-compute a tiny buffer on the chosen device
    # so a dead backend fails HERE, not mid-query (the
    # reference's Cuda.setDevice + freeZero acquisition, GpuDeviceManager
    # .scala:93-101)
    import jax.numpy as jnp
    try:
        x = jax.device_put(jnp.ones((8,)), devices[ordinal]) + 1
        x.block_until_ready()
    except Exception as e:
        raise PluginInitError(
            f"device {ordinal} acquisition failed: {e}") from e
    DeviceManager.initialize(conf)
    # the permits are a chip's: under the mesh a partition's task runs on its
    # own chip (one executor a chip), so each chip of the mesh admits as many
    permits = conf.get(CFG.CONCURRENT_TPU_TASKS)
    if conf.get(CFG.MESH_ENABLED):
        from spark_rapids_tpu.distributed.exchange import mesh_devices
        permits *= max(1, len(mesh_devices(conf)))
    TpuSemaphore.initialize(permits)


def driver_init(conf) -> dict:
    """Driver-side init; returns the context the reference propagates to
    executors through the plugin-context map (Plugin.scala:165)."""
    ctx = {}
    if conf.get(CFG.SHUFFLE_MANAGER_ENABLED):
        from spark_rapids_tpu.shuffle.heartbeat import (
            RapidsShuffleHeartbeatManager)
        ctx["heartbeat_manager"] = RapidsShuffleHeartbeatManager()
    return ctx


def bootstrap(conf, eager_device: bool = False) -> None:
    """Idempotent process-wide bootstrap, called by TpuSession. The device
    warmup is opt-in (spark.rapids.tpu.device.eagerInit or `eager_device`)
    because CPU-platform tests construct many sessions."""
    global _initialized
    with _lock:
        if _initialized:
            return
        _context.update(driver_init(conf))
        if eager_device or conf.get(CFG.DEVICE_EAGER_INIT):
            executor_init(conf)
        _initialized = True


def reset_for_tests() -> None:
    global _initialized
    with _lock:
        _initialized = False
        _context.clear()
