"""The runtime <-> device plane seam (_private/device_plane.py), on the
CPU: who gets the TPU backend, chip detection, the use_tpu gang default,
and where the compile cache lives. No test here imports jax in a process
that holds (fake) chips — the real thing is chip_smoke.py's job."""

import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import device_plane


def _lease_view():
    """What a task sees of the device plane, without touching jax."""
    import os
    import sys

    return {"platforms": os.environ.get("JAX_PLATFORMS"),
            "chip_ids": ray_tpu.get_tpu_chip_ids(),
            "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
            "jax_loaded": "jax" in sys.modules, "pid": os.getpid()}


# what a TPU host's environment asks of jax (the sealed chip machine's
# value); the driver's own jax read "cpu" long ago (conftest.py)
TPU_HOST_PLATFORMS = "tpu,cpu"


@pytest.fixture
def one_fake_chip(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "1")
    monkeypatch.setenv("JAX_PLATFORMS", TPU_HOST_PLATFORMS)
    ray_tpu.init(num_cpus=4)   # no resource arguments: detection decides
    yield
    ray_tpu.shutdown()


@pytest.mark.parametrize("node_platforms", ["cpu", None])
def test_chip_lease_gets_what_the_node_asks_of_jax(monkeypatch,
                                                   node_platforms):
    """A node started under JAX_PLATFORMS=cpu (this suite, always) never
    hands out the TPU backend, whatever it leases; one started with the
    variable unset leaves the choice to jax."""
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "1")
    if node_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    ray_tpu.init(num_cpus=2)
    try:
        view = ray_tpu.remote(_lease_view)
        held = ray_tpu.get(view.options(num_tpus=1).remote(), timeout=60)
        assert held["platforms"] == node_platforms
        assert held["chip_ids"] == [0]
        assert ray_tpu.get(view.remote(), timeout=60)["platforms"] == "cpu"
    finally:
        ray_tpu.shutdown()


def test_chip_lease_gets_device_plane_and_chipless_keeps_pin(one_fake_chip):
    assert ray_tpu.cluster_resources()["TPU"] == 1.0
    view = ray_tpu.remote(_lease_view)
    chipless = ray_tpu.get(view.remote(), timeout=60)
    assert chipless["platforms"] == "cpu" and chipless["chip_ids"] == []

    held = ray_tpu.get(view.options(num_tpus=1).remote(), timeout=60)
    assert held["platforms"] == TPU_HOST_PLATFORMS
    assert held["chip_ids"] == [0] and not held["jax_loaded"]
    # the whole host's chips: libtpu's own view of the host is left alone
    assert held["visible"] is None

    # the worker that claimed the chip is retired with its lease: the
    # next chip lease runs in a fresh process, once the first is gone,
    # and no chipless task ever lands on an un-pinned worker
    again = ray_tpu.get(view.options(num_tpus=1).remote(), timeout=60)
    assert again["pid"] != held["pid"] and again["chip_ids"] == [0]
    assert not device_plane.process_alive(held["pid"])
    for _ in range(4):
        v = ray_tpu.get(view.remote(), timeout=60)
        assert v["platforms"] == "cpu" and v["pid"] not in (
            held["pid"], again["pid"])


def _import_jax():
    import os

    import jax

    jax.devices()    # the backend starts, under the pool worker's CPU pin
    return os.getpid()


def test_chip_lease_never_lands_on_a_worker_that_ran_jax(monkeypatch):
    """Chip workers are born with their lease: the worker that just ran a
    jax task on the CPU is first in line for the next lease, and the chip
    lease passes it over for a process that never imported jax."""
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "1")
    monkeypatch.setenv("JAX_PLATFORMS", TPU_HOST_PLATFORMS)
    # no fast lanes: every task's worker goes back to the pool at once
    monkeypatch.setenv("RAY_TPU_FASTLANE", "0")
    ray_tpu.init(num_cpus=4)
    try:
        run_jax = ray_tpu.remote(_import_jax)
        view = ray_tpu.remote(_lease_view)
        tainted = ray_tpu.get(run_jax.remote(), timeout=120)
        assert ray_tpu.get(view.remote(), timeout=60)["pid"] == tainted
        for _ in range(2):           # a restarted holder is born fresh too
            held = ray_tpu.get(view.options(num_tpus=1).remote(), timeout=60)
            assert held["pid"] != tainted and not held["jax_loaded"]
            assert held["platforms"] == TPU_HOST_PLATFORMS
        # the pool keeps its worker for chipless work
        assert ray_tpu.get(run_jax.remote(), timeout=60) == tainted
    finally:
        ray_tpu.shutdown()


def _slow_to_exit(seconds):
    """A chip lease whose worker lingers ``seconds`` after it is told to
    shut down (worker_main leaves through os._exit)."""
    import os
    import time

    leave = os._exit

    def linger(code):
        time.sleep(seconds)
        leave(code)

    os._exit = linger
    return os.getpid()


@pytest.mark.parametrize("linger_s,grace_s", [
    (1.5, 30.0),     # leaves by itself, late
    (60.0, 0.5),     # does not leave: killed when its grace is over
])
def test_tpu_is_granted_again_only_when_its_holder_is_gone(
        one_fake_chip, monkeypatch, linger_s, grace_s):
    """The scalar TPU resource comes back together with the chip, once
    the retired worker's process has exited, never while it may still
    hold the device; a holder that will not leave is killed first."""
    import time

    from ray_tpu._private import raylet

    monkeypatch.setattr(raylet, "_CHIP_EXIT_GRACE_S", grace_s)
    pid = ray_tpu.get(ray_tpu.remote(_slow_to_exit).options(
        num_tpus=1).remote(linger_s), timeout=60)
    seen_alive_without_tpu = False
    deadline = time.time() + 20
    while True:
        free = ray_tpu.available_resources().get("TPU", 0.0)
        alive = device_plane.process_alive(pid)
        if free >= 1.0:
            # (alive was read after free: a holder that was gone then
            # is gone now)
            assert not alive, "TPU handed out under a live holder"
            break
        seen_alive_without_tpu |= alive
        assert time.time() < deadline, "the TPU never came back"
        time.sleep(0.01)
    assert seen_alive_without_tpu
    # and the next chip lease is granted
    again = ray_tpu.get(ray_tpu.remote(_lease_view).options(
        num_tpus=1).remote(), timeout=60)
    assert again["chip_ids"] == [0] and again["pid"] != pid


def test_use_tpu_gang_no_host_can_grant_fails_with_a_message(one_fake_chip):
    from ray_tpu.train import ScalingConfig, Trainer

    with pytest.raises(ValueError, match="no node can ever grant"):
        Trainer(lambda: None, scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"CPU": 1, "TPU": 4})).fit()


@pytest.mark.parametrize("requested,host_chips,want", [
    (None, 1.0, {"CPU": 1.0, "TPU": 1.0}),      # default: one host's chips
    (None, 4.0, {"CPU": 1.0, "TPU": 4.0}),
    ({"TPU": 2}, 4.0, {"TPU": 2}),
    (None, 0.0, ValueError),                    # no TPU host at all
    ({"TPU": 4}, 1.0, ValueError),
    ({"CPU": 1}, 4.0, ValueError),              # use_tpu without chips
])
def test_use_tpu_worker_resources(requested, host_chips, want):
    from ray_tpu.train import ScalingConfig

    scaling = ScalingConfig(use_tpu=True, resources_per_worker=requested)
    if want is ValueError:
        with pytest.raises(ValueError):
            scaling.worker_resources(host_chips)
    else:
        assert scaling.worker_resources(host_chips) == want


def test_chip_lease_on_a_worker_that_ran_jax_on_cpu_raises(monkeypatch):
    import jax  # noqa: F401 — this process's jax started under the pin

    monkeypatch.setenv("JAX_PLATFORMS", device_plane.CPU_PIN)
    monkeypatch.setenv("RAY_TPU_NODE_JAX_PLATFORMS", TPU_HOST_PLATFORMS)
    monkeypatch.delenv("RAY_TPU_CHIP_IDS", raising=False)
    with pytest.raises(RuntimeError, match="cannot be reclaimed"):
        device_plane.claim_chips([0])
    assert os.environ["JAX_PLATFORMS"] == device_plane.CPU_PIN
    assert "RAY_TPU_CHIP_IDS" not in os.environ


@pytest.mark.parametrize("chip_ids,bounds", [
    ([2], "1,1,1"), ([0, 1], "1,2,1"), ([0, 1, 2, 3], None)])
def test_subset_lease_confines_libtpu(monkeypatch, chip_ids, bounds):
    # a process that was un-pinned before it imported jax
    monkeypatch.setenv("JAX_PLATFORMS", TPU_HOST_PLATFORMS)
    monkeypatch.setenv("RAY_TPU_CHIP_IDS", "")
    monkeypatch.setenv("RAY_TPU_NODE_CHIPS", "4")
    for var in ("TPU_VISIBLE_CHIPS", *device_plane._BOUNDS_VARS,
                *device_plane._PROCESS_VARS):
        monkeypatch.delenv(var, raising=False)
    # the host's own bounds, as a TPU VM exports them
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    device_plane.claim_chips(chip_ids)
    ids = ",".join(map(str, chip_ids))
    assert os.environ["RAY_TPU_CHIP_IDS"] == ids
    if bounds is None:
        assert "TPU_VISIBLE_CHIPS" not in os.environ
        assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    else:
        assert os.environ["TPU_VISIBLE_CHIPS"] == ids
        assert {os.environ[v] for v in device_plane._BOUNDS_VARS} == {bounds}
        assert {os.environ[v] for v in device_plane._PROCESS_VARS} == {
            "1,1,1"}
    device_plane.release_chips()
    assert "RAY_TPU_CHIP_IDS" not in os.environ
    assert "TPU_VISIBLE_CHIPS" not in os.environ


@pytest.mark.parametrize("names,want", [
    (["accel0", "accel1", "accel2", "accel3", "null"], 4),
    (["vfio/0", "vfio/vfio"], 1),     # the control node is not a chip
    (["vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio"], 4),
    (["null", "shm"], 0),
])
def test_chip_detection_counts_device_nodes(tmp_path, monkeypatch, names,
                                            want):
    from ray_tpu._private import node

    for name in names:
        path = tmp_path / name
        path.parent.mkdir(exist_ok=True)
        path.touch()
    real = node._count_numbered
    monkeypatch.setattr(
        node, "_count_numbered",
        lambda d, prefix: real(str(tmp_path) + d[len("/dev"):], prefix))
    monkeypatch.delenv("RAY_TPU_FAKE_CHIPS", raising=False)
    assert node._detect_tpu_chips() == want


@pytest.fixture
def restored_cache_config():
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_obeys_the_environment(monkeypatch, tmp_path,
                                             restored_cache_config):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device_plane.enable_compilation_cache() == str(tmp_path)
    # jax's own reading of the variable stands: no directory set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(
        monkeypatch, restored_cache_config):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device_plane.enable_compilation_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert "/tmp" not in path
    assert str(os.getpid()) not in path and str(os.getuid()) not in \
        os.path.basename(path)


def test_driver_and_worker_imports_stay_off_jax():
    """The driver builds deployments and trainers, and the worker factory
    forks every pool worker: neither may load jax, or a chip's later
    holder inherits or fights an initialised backend."""
    code = (
        "import sys\n"
        "import ray_tpu, ray_tpu.serve, ray_tpu.train\n"
        "import ray_tpu._private.worker_main, ray_tpu._private.device_plane\n"
        "from ray_tpu.llm import build_llm_deployment\n"
        "from ray_tpu.train import Trainer, ScalingConfig, RunConfig\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize("fake_chips,node_platforms,want", [
    (None, "cpu", {}),                           # no chips at all
    ("1", "cpu", {}),                            # chips, node held to the CPU
    ("1", TPU_HOST_PLATFORMS, {"num_tpus": 1}),
    ("1", None, {"num_tpus": 1}),                # unset: jax's own choice
])
def test_llm_replicas_ask_for_a_chip_only_where_a_node_can_take_one(
        monkeypatch, fake_chips, node_platforms, want):
    from ray_tpu.llm.serve import LLMServer

    monkeypatch.delenv("RAY_TPU_FAKE_CHIPS", raising=False)
    if fake_chips is not None:
        monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", fake_chips)
    if node_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    else:
        monkeypatch.setenv("JAX_PLATFORMS", node_platforms)
    ray_tpu.init(num_cpus=2)
    try:
        assert LLMServer.replica_actor_options() == want
    finally:
        ray_tpu.shutdown()


def test_llm_deployment_serves_from_the_cpu_on_a_node_held_to_it(monkeypatch):
    """A host that shows chip device nodes, started under
    JAX_PLATFORMS=cpu (this suite on a TPU machine): serve.run asks for
    no chip, so the replicas neither refuse their backend nor queue on
    the TPU resource, and the replica reads its prefill path from the
    program it compiled."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "1")
    ray_tpu.init(num_cpus=4)
    try:
        assert ray_tpu.cluster_resources()["TPU"] == 1.0
        handle = serve.run(build_llm_deployment(
            "tiny", num_replicas=2,
            engine_config={"max_num_seqs": 2, "page_size": 4,
                           "num_pages": 64, "max_seq_len": 64}))
        out = ray_tpu.get(handle.options(method_name="completions").remote(
            {"prompt_ids": [5, 17, 99, 3], "temperature": 0.0,
             "max_tokens": 3}), timeout=300)
        assert len(out["choices"][0]["token_ids"]) == 3
        info = ray_tpu.get(handle.options(method_name="device_info").remote(
            {"prompt_len": 4}), timeout=300)
        assert info["platform"] == "cpu" and info["chip_ids"] == []
        assert info["prefill_attention"] == {
            "bucket": 16, "tpu_custom_calls": 0, "path": "blockwise"}
        assert ray_tpu.available_resources()["TPU"] == 1.0
        assert serve.status()[0]["num_replicas"] == 2
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_llm_server_refuses_a_chip_lease_on_another_backend(monkeypatch):
    from ray_tpu.llm import LLMServer

    monkeypatch.setenv("RAY_TPU_CHIP_IDS", "0")
    with pytest.raises(RuntimeError, match="refusing to serve"):
        LLMServer("tiny")
