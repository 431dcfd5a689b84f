"""Plugin bootstrap tests (SURVEY.md #1; reference Plugin.scala lifecycle)."""

import pytest

from spark_rapids_tpu import config as CFG
from spark_rapids_tpu import plugin as PL
from spark_rapids_tpu.config import RapidsConf


@pytest.fixture(autouse=True)
def fresh():
    PL.reset_for_tests()
    yield
    PL.reset_for_tests()


def test_driver_init_builds_heartbeat_manager():
    ctx = PL.driver_init(RapidsConf(
        {"spark.rapids.tpu.shuffle.enabled": "true"}))
    from spark_rapids_tpu.shuffle.heartbeat import RapidsShuffleHeartbeatManager
    assert isinstance(ctx["heartbeat_manager"], RapidsShuffleHeartbeatManager)


def test_executor_init_bad_ordinal_crashes_fast():
    with pytest.raises(PL.PluginInitError, match="out of range"):
        PL.executor_init(RapidsConf({"spark.rapids.tpu.device.ordinal": "99"}))


def test_executor_init_acquires_device():
    from spark_rapids_tpu.runtime.memory import DeviceManager
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
    conf = RapidsConf({"spark.rapids.tpu.sql.concurrentTpuTasks": "3"})
    PL.executor_init(conf)
    assert DeviceManager.get() is not None
    assert TpuSemaphore.get().max_concurrent == 3


def test_executor_init_gives_each_mesh_chip_its_permits():
    """Under the mesh a partition's task runs on its own chip: the permits
    are a chip's, so four chips admit four times as many tasks."""
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
    conf = RapidsConf({"spark.rapids.tpu.sql.concurrentTpuTasks": "3",
                       "spark.rapids.tpu.mesh.enabled": "true",
                       "spark.rapids.tpu.mesh.devices": "4"})
    try:
        PL.executor_init(conf)
        assert TpuSemaphore.get().max_concurrent == 12
    finally:
        PL.executor_init(RapidsConf())
    assert TpuSemaphore.get().max_concurrent == 2


def test_bootstrap_idempotent_and_eager():
    conf = RapidsConf({"spark.rapids.tpu.device.eagerInit": "true"})
    PL.bootstrap(conf)
    PL.bootstrap(RapidsConf({"spark.rapids.tpu.device.ordinal": "99"}))
    # second call is a no-op: the bad ordinal never ran


def test_session_triggers_bootstrap():
    from spark_rapids_tpu.session import TpuSession
    TpuSession()
    assert PL._initialized


def test_bootstrap_retains_context():
    PL.bootstrap(RapidsConf({"spark.rapids.tpu.shuffle.enabled": "true"}))
    from spark_rapids_tpu.shuffle.heartbeat import RapidsShuffleHeartbeatManager
    assert isinstance(PL.context().get("heartbeat_manager"),
                      RapidsShuffleHeartbeatManager)


def test_trace_conf_wires_annotations(tmp_path):
    """spark.rapids.tpu.sql.trace.enabled must actually flip the tracing
    module (it was a dead conf); a traced query still runs."""
    import pyarrow as pa
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.runtime import tracing
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.tpu.sql.trace.enabled": "true"})
    assert tracing._enabled
    df = s.create_dataframe({"a": pa.array([1, 2, 3], pa.int64())})
    assert df.filter(F.col("a") > 1).collect().num_rows == 2
    TpuSession()                     # default session must NOT clobber it
    assert tracing._enabled
    TpuSession({"spark.rapids.tpu.sql.trace.enabled": "false"})
    assert not tracing._enabled
