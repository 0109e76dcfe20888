"""Profiling & memory-attribution plane (ref: Google-Wide Profiling,
Ren et al., IEEE Micro 2010; `ray memory` / py-spy): folded-stack
merging, sampler overhead, cluster flamegraphs, object-store byte
attribution, leak-suspect flagging, submit-path stage timers."""

import os
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics, stacks, state


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


# ------------------------------------------------------------- folded stacks

def test_folded_merge_and_speedscope():
    a = {"r;f1;f2": 3, "r;f1": 1}
    b = {"r;f1;f2": 2, "x;y": 5}
    merged = stacks.merge_folded(a, b)
    assert merged == {"r;f1;f2": 5.0, "r;f1": 1.0, "x;y": 5.0}
    # collapsed text: descending count, ties broken by key
    lines = stacks.collapse_lines(merged).splitlines()
    assert lines == ["r;f1;f2 5", "x;y 5", "r;f1 1"]
    doc = stacks.speedscope(merged, name="t", hz=10.0)
    prof = doc["profiles"][0]
    assert prof["type"] == "sampled"
    assert len(prof["samples"]) == len(prof["weights"]) == 3
    # weights scale to seconds at hz: 11 samples / 10 Hz
    assert sum(prof["weights"]) == pytest.approx(1.1)
    frames = [f["name"] for f in doc["shared"]["frames"]]
    for label in ("r", "f1", "f2", "x", "y"):
        assert label in frames
    for sample in prof["samples"]:
        assert all(0 <= i < len(frames) for i in sample)


def _busy_hotspot(deadline: float) -> int:
    count = 0
    while time.perf_counter() < deadline:
        count += 1
    return count


def test_sampler_sees_hot_function_with_bounded_overhead():
    """The sampler must (a) attribute a busy loop to the function
    running it, in BOTH wall and cpu views, and (b) not slow the loop
    down materially (the always-on claim, asserted generously for CI)."""
    baseline = _busy_hotspot(time.perf_counter() + 0.4)
    sampler = stacks.StackSampler(100.0, name="stack_sampler_test").start()
    try:
        sampled = _busy_hotspot(time.perf_counter() + 0.4)
    finally:
        sampler.stop()
    snap = sampler.snapshot()
    assert snap["samples"] > 5
    assert any("_busy_hotspot" in key for key in snap["wall"])
    assert any("_busy_hotspot" in key for key in snap["cpu"])
    # generous bound: 100 Hz sampling must cost well under half the
    # loop's throughput (in practice it is a few percent)
    assert sampled >= 0.4 * baseline, (sampled, baseline)


def test_sampler_annotation_roots_and_idle_split():
    """annotate() roots the folded key (the scheduling-class handle the
    GCS merges by) and a sleeping thread is wall-only, never cpu."""
    import threading

    stop = threading.Event()
    waiter = threading.Thread(target=stop.wait, name="test_waiter",
                              daemon=True)
    waiter.start()
    idents = {waiter.ident}
    sampler = stacks.StackSampler(
        50.0, annotate=lambda i: "task:marked" if i in idents else None,
        name="stack_sampler_test2")
    try:
        time.sleep(0.05)
        sampler.sample_once()
        snap = sampler.snapshot()
    finally:
        stop.set()
        waiter.join(timeout=2)
    marked = [k for k in snap["wall"] if k.startswith("task:marked;")]
    assert marked, snap["wall"]
    # the waiter is parked in Event.wait → excluded from the cpu view
    assert not any(k.startswith("task:marked;") for k in snap["cpu"])


# --------------------------------------------------------- cluster profile

def test_profile_cluster_names_hot_function(ray_cluster):
    @ray_tpu.remote
    def spin_hot(sec):
        t_end = time.time() + sec
        x = 0
        while time.time() < t_end:
            x += 1
        return x

    # a worker is up and has the function before the clock starts: on a
    # slow host the first worker registers later than the sleep below
    assert ray_tpu.get(spin_hot.remote(0.0), timeout=60) == 0
    ref = spin_hot.remote(4.0)
    time.sleep(0.5)  # let the worker pick it up
    prof = state.profile_cluster(duration_s=1.0, hz=50.0)
    assert ray_tpu.get(ref, timeout=60) > 0
    assert prof["samples"] > 0
    assert prof["workers"] >= 1
    # the busy task function shows up in the merged wall stacks, and its
    # samples roll up under its task:<fn> scheduling class
    assert any("spin_hot" in key for key in prof["wall"]), \
        sorted(prof["wall"])[:5]
    assert any("spin_hot" in cls for cls in prof["by_class"]), \
        prof["by_class"]
    # per-node maps re-merge to the overall profile
    remerged = stacks.merge_folded(*prof["per_node"].values())
    assert sum(remerged.values()) == pytest.approx(
        sum(prof["wall"].values()))


# ------------------------------------------------------- memory attribution

def test_memory_report_attributes_store_bytes(ray_cluster):
    """Driver-held plasma objects must be attributed (>=95% of live
    store bytes) to their holder with ref_type local_ref."""
    blob = os.urandom(1 << 20)
    refs = [ray_tpu.put(blob) for _ in range(4)]
    rep = state.memory_report()
    cluster = rep["cluster"]
    assert cluster["used_bytes"] >= 4 * (1 << 20)
    assert cluster["attributed_fraction"] >= 0.95, cluster
    by_oid = {o["object_id"]: o for o in rep["objects"]}
    for ref in refs:
        entry = by_oid.get(ref.hex())
        assert entry is not None, (ref.hex(), sorted(by_oid))
        assert entry["ref_type"] == "local_ref"
        assert "driver" in entry["owners"]
        assert not entry["leak_suspect"]
    # store ground truth: by_ref_type sums match the node's used bytes
    # (tolerance: zero-size objects occupy one page on disk)
    for node in rep["nodes"]:
        diff = abs(sum(node["by_ref_type"].values())
                   - node["used_bytes"])
        assert diff <= max(8192, 0.01 * node["used_bytes"]), node
    del refs


def test_leak_suspect_on_orphaned_pinned_object(ray_cluster):
    """An object pinned at the raylet that no live worker claims (the
    owner died / dropped it without unpinning) must be flagged."""
    from ray_tpu import _worker_api
    from ray_tpu._private.ids import ObjectID

    core = _worker_api.core()
    oid = ObjectID.from_random()
    core.store.put(oid, b"L" * 4096)  # ownerless: bypasses ref tables
    state._raylet_call(None, "pin_objects", {"object_ids": [oid]})
    try:
        rep = state.memory_report(leak_age_s=-1.0)
        suspects = {o["object_id"] for o in rep["leak_suspects"]}
        assert oid.hex() in suspects, rep["leak_suspects"]
        entry = next(o for o in rep["objects"]
                     if o["object_id"] == oid.hex())
        assert entry["ref_type"] == "pinned"
        assert entry["pinned"] >= 1
        # a claimed object of the same age is NOT a suspect
        held = ray_tpu.put(b"H" * 4096)
        rep2 = state.memory_report(leak_age_s=-1.0)
        assert held.hex() not in {o["object_id"]
                                  for o in rep2["leak_suspects"]}
        del held
    finally:
        state._raylet_call(None, "unpin_objects", {"object_ids": [oid]})
        core.store.delete(oid)


def test_worker_heap_in_memory_report(ray_cluster):
    rep = state.memory_report()
    workers = rep["workers"]
    assert workers, rep.get("errors")
    modes = {w["mode"] for w in workers}
    assert "driver" in modes
    for w in workers:
        heap = w["heap"]
        assert heap["kind"] in ("tracemalloc", "rss")
        assert heap["current_bytes"] > 0


# --------------------------------------------------- submit stage timers

def test_submit_stage_timers_partition_submit_wall(ray_cluster):
    """The sync stages partition submit_task: their sums must land
    within 20% of the recorded end-to-end `total` stage, and the
    histogram must have observed every submit."""
    @ray_tpu.remote
    def nop():
        return None

    ray_tpu.get([nop.remote() for _ in range(20)], timeout=60)  # warmup
    base = metrics.snapshot_local("submit_stage_seconds")
    n = 300
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(n)]
    wall = time.perf_counter() - t0
    snap = metrics.snapshot_local("submit_stage_seconds")
    ray_tpu.get(refs, timeout=120)

    def _deltas(stat):
        out = {}
        for key, v in snap.items():
            if f"__stat__={stat}" not in key or "{" not in key:
                continue
            tags = dict(p.split("=", 1)
                        for p in key[key.index("{") + 1:-1].split(","))
            if "stage" in tags:
                out[tags["stage"]] = v - base.get(key, 0.0)
        return out

    sums, counts = _deltas("sum"), _deltas("count")
    sync_stages = ("export_fn", "serialize", "spec_mint", "bookkeeping",
                   "task_event", "dispatch")
    for stage in sync_stages + ("total",):
        assert counts.get(stage, 0) == n, (stage, counts)
    sync_sum = sum(sums[s] for s in sync_stages)
    total = sums["total"]
    assert total > 0
    # partition invariant: consecutive perf_counter marks, no gaps
    assert abs(sync_sum - total) / total < 0.2, (sync_sum, total)
    # and the recorded total tracks the measured submit wall
    assert total <= wall * 1.05, (total, wall)
    assert total >= 0.2 * wall, (total, wall)
