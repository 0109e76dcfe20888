"""Actor API completeness: async actors, detached lifetime, multi-driver
attach (ref: python/ray/tests/test_asyncio.py, test_actor_advanced.py
detached-actor suites)."""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture
def ray_cluster():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def test_async_actor_concurrency(ray_cluster):
    """Two calls must interleave at await points: the first parks on an
    asyncio.Event that only the second sets — a serialized actor would
    deadlock here."""
    @ray_tpu.remote
    class Signal:
        def __init__(self):
            self.event = asyncio.Event()

        async def wait(self):
            await self.event.wait()
            return "released"

        async def fire(self):
            self.event.set()
            return "fired"

    sig = Signal.remote()
    waiter = sig.wait.remote()
    time.sleep(0.5)  # let wait() park on the event first
    assert ray_tpu.get(sig.fire.remote(), timeout=30) == "fired"
    assert ray_tpu.get(waiter, timeout=30) == "released"


def test_async_actor_many_concurrent_calls(ray_cluster):
    @ray_tpu.remote
    class Gate:
        def __init__(self):
            self.entered = 0
            self.event = asyncio.Event()

        async def enter(self):
            self.entered += 1
            await self.event.wait()
            return self.entered

        async def open(self):
            self.event.set()
            return True

    gate = Gate.remote()
    refs = [gate.enter.remote() for _ in range(20)]
    deadline = time.time() + 30
    # all 20 must be parked inside the actor before the gate opens
    while time.time() < deadline:
        time.sleep(0.1)
        if ray_tpu.get(gate.open.remote(), timeout=30):
            break
    out = ray_tpu.get(refs, timeout=60)
    assert max(out) == 20


def test_async_actor_exception(ray_cluster):
    @ray_tpu.remote
    class Bad:
        async def boom(self):
            raise ValueError("async boom")

    bad = Bad.remote()
    with pytest.raises(ray_tpu.exceptions.TaskError, match="async boom"):
        ray_tpu.get(bad.boom.remote(), timeout=30)


def test_detached_actor_survives_driver_exit():
    """Driver 1 creates a detached actor and detaches; driver 2 attaches
    to the same cluster and finds it alive with state intact. Non-detached
    actors die with their driver."""
    cluster = Cluster(head_node_args={"resources": {"CPU": 2.0}})
    try:
        # driver 1
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

        svc = Counter.options(name="svc", lifetime="detached").remote()
        assert ray_tpu.get(svc.incr.remote(), timeout=60) == 1
        tmp = Counter.options(name="tmp").remote()
        assert ray_tpu.get(tmp.incr.remote(), timeout=60) == 1
        ray_tpu.shutdown()   # detach: the cluster keeps running

        # driver 2
        ray_tpu.init(address=cluster.address)
        svc2 = ray_tpu.get_actor("svc")
        assert ray_tpu.get(svc2.incr.remote(), timeout=60) == 2  # state kept
        with pytest.raises(ValueError):
            ray_tpu.get_actor("tmp")  # non-detached: died with driver 1
        ray_tpu.shutdown()
    finally:
        cluster.shutdown()


def test_detached_requires_name(ray_cluster):
    @ray_tpu.remote
    class A:
        pass

    with pytest.raises(ValueError, match="must be named"):
        A.options(lifetime="detached").remote()


@pytest.mark.parametrize("bound, placed, alive", [
    (60.0, True, True), (1.0, True, False), (60.0, False, False)],
    ids=["waits-behind-it", "a-constructor-that-hangs",
         "a-lease-nobody-grants"])
def test_a_call_waits_behind_a_constructor_the_gcs_says_is_running(
        ray_cluster, monkeypatch, bound, placed, alive):
    """A constructor that outlasts the wait's timeout (a serve replica
    compiling its programs with an empty cache) is not a dead actor: while
    the GCS says a worker runs it the wait goes on, up to the bound. An
    actor no worker can be leased for is PENDING_CREATION too, and the
    wait's own timeout is raised for it as before."""
    from ray_tpu._private import gcs
    from ray_tpu._worker_api import core

    monkeypatch.setattr(gcs, "CONSTRUCTOR_TIMEOUT_S", bound)

    @ray_tpu.remote
    class Slow:
        def __init__(self):
            time.sleep(3.0)

        def ping(self):
            return "pong"

    actor = (Slow if placed else Slow.options(
        resources={"a resource no node has": 1})).remote()
    if placed:
        # (a worker's start is not the constructor: the wait's timeout is
        # 120 s in the runtime and half a second here)
        deadline = time.time() + 60
        while not core().io.run(core().gcs.call(
                "get_actor", {"actor_id": actor._actor_id}, timeout=30),
                timeout=60).constructor_running:
            assert time.time() < deadline
            time.sleep(0.05)
    wait = core()._wait_actor_alive(actor._actor_id, timeout=0.5)
    if alive:
        assert core().io.run(wait, timeout=60).state == "ALIVE"
        assert ray_tpu.get(actor.ping.remote(), timeout=30) == "pong"
    else:
        with pytest.raises(asyncio.TimeoutError):
            core().io.run(wait, timeout=60)
        if not placed:
            ray_tpu.kill(actor)
