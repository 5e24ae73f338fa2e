"""Test harness: run everything on an 8-way virtual CPU device mesh.

Must set the env vars BEFORE jax is imported anywhere (SURVEY.md §4:
device-count spoofing via --xla_force_host_platform_device_count).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # override any preset TPU platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected ≥8 spoofed CPU devices, got {len(devs)}"
    return devs


# ProcessCluster fixture ports: below the kernel ephemeral floor
# (32768) so a transient client socket can never squat a base, spaced
# wider than any per-fleet spread (driver + 8 executors × 40)
_CLUSTER_PORT = [24200]


def _next_cluster_port() -> int:
    p = _CLUSTER_PORT[0]
    _CLUSTER_PORT[0] += 500
    return p


@pytest.fixture
def cluster(tmp_path):
    """A REAL 2-process cluster: driver in this process + two full
    TpuShuffleManager executor processes over TCP sockets.  Tests drive
    it through the pipe command protocol (register/write/read); obs
    dumps (flight recorder + logs) land in the workdir and are merged
    at teardown."""
    from sparkrdma_tpu.transport.simfleet import ProcessCluster

    c = ProcessCluster(
        2, _next_cluster_port(),
        conf={
            "spark.shuffle.tpu.partitionLocationFetchTimeout": "15s",
            "spark.shuffle.tpu.connectTimeout": "10s",
            "spark.shuffle.tpu.fetchRetryWaitMs": "100ms",
        },
        workdir=str(tmp_path / "cluster"),
    )
    yield c
    c.stop()
    c.collect()


@pytest.fixture(scope="session", autouse=True)
def collect_flight_recorder_dump():
    """Fleet-wide observability collection: with
    ``SPARKRDMA_TPU_OBS_DUMP_DIR`` set, this process retains the
    flight recorder for the whole session and leaves one dump at exit;
    merge the per-process files with
    ``python tools/trace_report.py <dir>/*.json`` for one
    cross-process trace of the run.  Opt-in only — holding the
    recorder open changes the (normally off-by-default) enabled flag
    some lifecycle assertions check, so this is a debugging mode, not
    part of the default gate."""
    dump_dir = os.environ.get("SPARKRDMA_TPU_OBS_DUMP_DIR")
    if not dump_dir:
        yield
        return
    from sparkrdma_tpu.obs import RECORDER
    from sparkrdma_tpu.obs.collect import write_dump

    RECORDER.retain(ring_size=1 << 16)
    yield
    write_dump(
        os.path.join(dump_dir, f"flightrec-session-{os.getpid()}.json"),
        reason="session_end",
    )
    RECORDER.release()
