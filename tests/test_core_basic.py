"""Core API tests: put/get/wait, tasks, actors (ref: python/ray/tests/test_basic.py)."""

import time

import numpy as np
import pytest

import ray_tpu


def test_put_get(ray_start_regular):
    ref = ray_tpu.put(42)
    assert ray_tpu.get(ref) == 42
    ref2 = ray_tpu.put({"a": [1, 2, 3], "b": "x"})
    assert ray_tpu.get(ref2) == {"a": [1, 2, 3], "b": "x"}


def test_put_get_large_numpy(ray_start_regular):
    arr = np.random.default_rng(0).standard_normal((512, 512)).astype(np.float32)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(arr, out)


def test_simple_task(ray_start_regular):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_task_with_kwargs_and_refs(ray_start_regular):
    @ray_tpu.remote
    def combine(a, b=0, c=0):
        return a + b + c

    x = ray_tpu.put(10)
    assert ray_tpu.get(combine.remote(x, b=5, c=1)) == 16


def test_task_large_args_and_returns(ray_start_regular):
    @ray_tpu.remote
    def double(arr):
        return arr * 2

    arr = np.ones((256, 1024), dtype=np.float32)
    out = ray_tpu.get(double.remote(arr))
    np.testing.assert_array_equal(out, arr * 2)


def test_chained_tasks(ray_start_regular):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(5):
        ref = inc.remote(ref)
    assert ray_tpu.get(ref) == 6


def test_multiple_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates(ray_start_regular):
    @ray_tpu.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(ray_tpu.exceptions.TaskError, match="kaboom"):
        ray_tpu.get(boom.remote())


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(1.0)
        return "slow"

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([f, s], num_returns=1, timeout=5)
    assert ready == [f]
    assert not_ready == [s]
    assert ray_tpu.get(s) == "slow"


def test_parallel_tasks(ray_start_regular):
    @ray_tpu.remote
    def sq(i):
        return i * i

    refs = [sq.remote(i) for i in range(20)]
    assert ray_tpu.get(refs) == [i * i for i in range(20)]


def test_nested_tasks(ray_start_regular):
    @ray_tpu.remote
    def inner(x):
        return x * 10

    @ray_tpu.remote
    def outer(x):
        import ray_tpu as rt

        return rt.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(4)) == 41


class _Counter:
    def __init__(self, start=0):
        self.value = start

    def inc(self, by=1):
        self.value += by
        return self.value

    def read(self):
        return self.value


def test_actor_basic(ray_start_regular):
    Counter = ray_tpu.remote(_Counter)
    counter = Counter.remote(5)
    assert ray_tpu.get(counter.inc.remote()) == 6
    assert ray_tpu.get(counter.inc.remote(10)) == 16
    assert ray_tpu.get(counter.read.remote()) == 16


def test_actor_ordering(ray_start_regular):
    Counter = ray_tpu.remote(_Counter)
    counter = Counter.remote()
    refs = [counter.inc.remote() for _ in range(50)]
    assert ray_tpu.get(refs) == list(range(1, 51))


def test_named_actor(ray_start_regular):
    Counter = ray_tpu.remote(_Counter)
    counter = Counter.options(name="the_counter").remote(100)
    ray_tpu.get(counter.read.remote())  # ensure alive
    again = ray_tpu.get_actor("the_counter")
    assert ray_tpu.get(again.read.remote()) == 100


def test_kill_actor(ray_start_regular):
    Counter = ray_tpu.remote(_Counter)
    counter = Counter.remote()
    assert ray_tpu.get(counter.inc.remote()) == 1
    ray_tpu.kill(counter)
    with pytest.raises((ray_tpu.exceptions.ActorDiedError,
                        ray_tpu.exceptions.RayTpuError)):
        ray_tpu.get(counter.inc.remote(), timeout=10)


def test_actor_constructor_error(ray_start_regular):
    @ray_tpu.remote
    class Bad:
        def __init__(self):
            raise RuntimeError("bad init")

        def f(self):
            return 1

    bad = Bad.remote()
    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        ray_tpu.get(bad.f.remote(), timeout=20)


def test_actor_handle_in_task(ray_start_regular):
    Counter = ray_tpu.remote(_Counter)
    counter = Counter.remote()

    @ray_tpu.remote
    def bump(handle):
        import ray_tpu as rt

        return rt.get(handle.inc.remote())

    assert ray_tpu.get(bump.remote(counter)) == 1
    assert ray_tpu.get(counter.read.remote()) == 1


def test_object_store_concurrent_get(tmp_path):
    """Concurrent gets of a foreign-sealed object must not double-count."""
    import os
    import threading
    import numpy as np
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_store import SharedObjectStore

    a = SharedObjectStore("rtpu_test_ccg", 1 << 24)
    b = SharedObjectStore("rtpu_test_ccg", 1 << 24, create_dir=False)
    try:
        oid = ObjectID(os.urandom(ObjectID.SIZE))
        a.put(oid, b"x" * 4096)
        results = []

        def reader():
            results.append(bytes(b.get(oid)))

        threads = [threading.Thread(target=reader) for _ in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert all(r == b"x" * 4096 for r in results)
        assert b.used_bytes() == 4096, b.used_bytes()
    finally:
        a.destroy()


@pytest.mark.parametrize("via", ["get", "wait"])
def test_async_get_survives_a_reply_landing_between_its_two_reads(
        ray_start_regular, via):
    """A fast-lane reply thread stores a task's value and only then
    un-registers the task. An await that read "not stored" and then "not
    pending" around that moment took the object for a foreign one and
    parked on the raylet directory forever (small returns never enter
    it): one streamed serve response in a few dozen hung that way."""
    import threading

    from ray_tpu._private import serialization as ser
    from ray_tpu._private.ids import ObjectID

    core = ray_tpu._worker_api.core()
    oid = ObjectID.from_random()
    core._lane_events[oid] = threading.Event()   # in flight on a lane
    real_contains = core.memory_store.contains
    landed = []

    def contains(o):
        if o == oid and not landed:
            # the reply lands right AFTER this read says "not stored"
            landed.append(True)
            core.memory_store.put(oid, ser.serialize("late"))
            core._lane_events.pop(oid, None)
            return False
        return real_contains(o)

    core.memory_store.contains = contains
    try:
        if via == "get":
            assert core.io.run(core._get([oid], None), timeout=20) == ["late"]
        else:
            assert core.io.run(core._wait([oid], 1, None),
                               timeout=20) == [oid]
    finally:
        core.memory_store.contains = real_contains
        core._lane_events.pop(oid, None)
        core.memory_store.delete(oid)


def test_owner_never_calls_an_object_gone_while_its_reply_lands(
        ray_start_regular):
    """The serving side of the same race: an owner that read "not
    stored" and then "not pending" around a landing reply answered
    "gone", and the borrower — a worker resolving a task's arguments —
    parked on the raylet directory for good (an actor call that takes
    another actor's fresh return hung about one time in four under
    load)."""
    import threading

    from ray_tpu._private import serialization as ser
    from ray_tpu._private.ids import ObjectID

    core = ray_tpu._worker_api.core()
    oid = ObjectID.from_random()
    core._lane_events[oid] = threading.Event()   # in flight on a lane
    real_get = core.memory_store.get

    def get(o):
        if o == oid and core._lane_events.pop(oid, None) is not None:
            # the reply lands right AFTER this read says "not stored"
            core.memory_store.put(oid, ser.serialize("late"))
            return None
        return real_get(o)

    core.memory_store.get = get
    try:
        fetch = lambda: core.io.run(  # noqa: E731
            core._handle_fetch_object({"object_id": oid}, None), timeout=20)
        assert fetch()["status"] == "pending"    # the borrower asks again
        assert fetch()["status"] == "ok"
    finally:
        core.memory_store.get = real_get
        core._lane_events.pop(oid, None)
        core.memory_store.delete(oid)
