"""Multi-controller (multi-host analog) integration: two real processes
rendezvous through ``multihost.initialize`` and run cross-process
collectives — psum and the tiled all_to_all the shuffle exchange rides —
over a global mesh (SURVEY.md §2 distributed-backend inventory row; on a
pod the same code paths carry ICI in-slice and DCN across slices)."""

import os
import socket
import subprocess
import sys

import pytest

from sparkrdma_tpu.parallel.multihost import (
    supports_multiprocess_collectives,
)


@pytest.fixture(autouse=True, scope="module")
def _multiprocess_collectives():
    """The workers strip the harness's JAX_PLATFORMS/XLA_FLAGS pins and
    get jax's real default backend — on a CPU-only host that backend
    cannot run cross-process collectives, so these tests skip with the
    reason spelled out instead of failing 150-240s into a doomed
    rendezvous.  Probed here, not at import: the probe starts a child
    process that initializes the default backend."""
    if not supports_multiprocess_collectives():
        pytest.skip(
            "default jax backend has no multiprocess collectives "
            "(CPU backend: 'Multiprocess computations aren't "
            "implemented') — needs a real TPU/GPU multi-controller "
            "runtime"
        )


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(worker_file: str, n_procs: int, timeout: int,
                 ok_msg: str, sigkilled: dict = {}) -> None:
    """``sigkilled`` maps a process id that SIGKILLs itself mid-run to
    the ok-message it must have printed BEFORE dying (its exit code is
    then -SIGKILL, not 0)."""
    import signal

    worker = os.path.join(os.path.dirname(__file__), worker_file)
    port = _free_port()
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in range(n_procs)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"{worker_file} hung")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if pid in sigkilled:
            assert p.returncode == -signal.SIGKILL, (
                f"victim proc {pid} exited {p.returncode}, "
                f"expected SIGKILL:\n{out}"
            )
            assert f"proc {pid}: {sigkilled[pid]}" in out, out
            continue
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid}: {ok_msg}" in out, out


def test_two_process_collectives():
    _run_workers(
        "multihost_worker.py", 2, 150, "multihost collectives OK"
    )


def test_four_process_windowed_plane():
    """The unified plane at 4 OS processes: uneven plan windows,
    reducer-issued reads, straggler overlap — then an INDUCED EXECUTOR
    LOSS (process 3 SIGKILLs itself) whose pending windowed readers
    must fail promptly on every survivor via heartbeat prune +
    membership-epoch plan dooming over real TCP."""
    _run_workers(
        "multihost4_worker.py", 4, 240,
        "windowed executor-loss fails prompt OK",
        sigkilled={3: "4-process windowed plane OK"},
    )
