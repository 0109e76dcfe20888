"""Test fixtures (ref: python/ray/tests/conftest.py fixture ladder).

Device-plane tests run on a virtual 8-device CPU mesh so mesh/collective
logic is exercised without TPU hardware (SURVEY §4.4). A machine with a
chip exports ``JAX_PLATFORMS=tpu,cpu``; tests must NEVER take the chip
(it belongs to one process at a time), so the platform is forced back to
cpu here, before any backend is initialized.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Spawned drivers / CLI heads import ray_tpu by module name; a clean
# shell has no PYTHONPATH entry for the repo root, so child processes
# would die with ModuleNotFoundError even though pytest itself found
# the package via rootdir. Prepend the repo root for every subprocess.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)
_pp = os.environ.get("PYTHONPATH", "")
if _REPO_ROOT not in _pp.split(os.pathsep):
    os.environ["PYTHONPATH"] = (_REPO_ROOT + os.pathsep + _pp) if _pp else _REPO_ROOT

import jax

# jax read its platforms when it was imported (a plugin or sitecustomize
# may have imported it before the line above ran); this update lands
# before any backend is initialized, so tests stay CPU-only.
jax.config.update("jax_platforms", "cpu")

import signal
import threading

import pytest

# Per-test wall-clock guard (ref: the reference root pytest.ini's 180 s
# default-timeout): one wedged test must not hang a whole CI round.
# pytest-timeout isn't vendored in this image, so a SIGALRM in the main
# thread raises inside whatever the test is blocked on.
_TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT_S", "180"))


import faulthandler

if hasattr(signal, "SIGUSR1"):
    # `kill -USR1 <pytest pid>` dumps every thread's stack — the hung-
    # test debugging hook (ref: the reference's py-spy dashboard hook)
    faulthandler.register(signal.SIGUSR1, all_threads=True)


def pytest_configure(config):
    # The tier-1 gate (ROADMAP) runs `-m 'not slow'` under a hard wall-
    # clock budget; convergence soaks that need tens of seconds each live
    # in the slow lane and run via `-m slow` (or an unfiltered invocation).
    config.addinivalue_line(
        "markers", "slow: convergence soak excluded from the tier-1 fast gate")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # wraps setup+call+teardown: a wedged fixture (cluster shutdown,
    # module-scoped init) is guarded too, not just the test body
    if (_TEST_TIMEOUT_S > 0 and hasattr(signal, "SIGALRM")
            and threading.current_thread() is threading.main_thread()):
        def _on_alarm(signum, frame):
            faulthandler.dump_traceback(all_threads=True)
            raise TimeoutError(
                f"test exceeded {_TEST_TIMEOUT_S}s (RAY_TPU_TEST_TIMEOUT_S)")

        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(_TEST_TIMEOUT_S)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
    else:
        yield


@pytest.fixture
def ray_start_regular():
    """Fresh single-node cluster per test (ref: conftest.py:580)."""
    import ray_tpu

    if ray_tpu.is_initialized():  # a prior module's teardown misfired
        ray_tpu.shutdown()
    info = ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    yield info
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    """Module-scoped cluster (ref: ray_start_regular_shared conftest.py:597)."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh8():
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 host devices"
    yield devices[:8]
