"""Scalability-envelope benchmarks.

Mirrors the reference's published envelope (ref:
release/benchmarks/README.md:9-31 — 1M queued tasks, 10k+ concurrent
tasks, 40k actors, 1 GiB broadcast, 10k object args, 100 GiB objects)
scaled to the host this runs on. Each family prints one JSON line with
the depth actually reached, so the recorded number is the measured
number, never an aspiration.

Families:
  * queued    — N tasks submitted into backlog on one node, then drained
  * sched     — native lease queue driven directly at 1M queued leases
  * inflight  — N simultaneously in-flight (sleeping) task invocations
  * actors    — N live actors created, pinged, then released
  * broadcast — 1 GiB object pulled by every node of a 4-node cluster
  * getmany   — one ray.get over 10k store objects
  * bigobj    — a single multi-GiB numpy object round-trip
  * tail      — task + serve p50/p99/p999 with one slow node/replica,
                hedged speculative execution off vs on
  * serve_prefix — fleet KV plane: prefix-affinity routing TTFT
                (off/on, cold/warm) + disaggregated prefill/decode
                handoff overhead and TPOT isolation
  * serve_spec — speculative decoding plane: generated tok/s and TPOT
                p99 under concurrent greedy loadgen, sequential decode
                vs draft/verify with aligned and adversarial drafters
  * slo       — SLO observability plane: open-loop multi-tenant loadgen
                attainment + time-to-fast-burn-alert under an injected
                slow replica
  * train_goodput — training goodput plane: MFU / tok-per-chip baseline
                with the ledger's badput-by-cause phase breakdown on a
                short tiny-config fit
  * submit    — driver submit-path per-stage latency breakdown (the
                submit_stage_seconds histogram) + always-on sampling
                profiler overhead at profiling_sample_hz=1

Run:  python bench_envelope.py [family ...] [--quick]
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

QUICK = "--quick" in sys.argv
# --moderate: shallower depths for a bounded wall clock; no flag runs
# every family at full depth (a CPU script run by hand, no record kept)
MODERATE = "--moderate" in sys.argv
FAMILIES = [a for a in sys.argv[1:] if not a.startswith("--")]


def emit(name, **fields):
    rec = {"bench": name}
    rec.update({k: (round(v, 2) if isinstance(v, float) else v)
                for k, v in fields.items()})
    print(json.dumps(rec), flush=True)
    return rec


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


# ---------------------------------------------------------------- queued
def bench_queued(results, n=1_000_000):
    """Submit n trivial tasks into backlog, then drain them all.

    Reference-envelope depth (release/benchmarks/README.md:30 — 1M
    queued on a 64-core box): 1M END-TO-END submissions here, not the
    native-queue microbench's 1M (envelope_native_sched covers that
    layer separately). Driver RSS is reported so ref-list growth stays
    an observed quantity.
    """
    import ray_tpu as ray

    @ray.remote
    def nop():
        return None

    n = 2_000 if QUICK else (200_000 if MODERATE else n)
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(n)]
    t_submit = time.perf_counter() - t0
    rss_peak = _rss_mb()
    t0 = time.perf_counter()
    # drain in slices so one giant get() doesn't build a 100k-future list twice
    for i in range(0, n, 10_000):
        ray.get(refs[i:i + 10_000])
    t_drain = time.perf_counter() - t0
    results.append(emit(
        "envelope_queued_tasks", depth=n,
        submit_per_s=n / t_submit, drain_per_s=n / t_drain,
        driver_rss_mb=rss_peak))


# ---------------------------------------------------------------- sched
def bench_sched(results, n=1_000_000):
    """Drive the native lease queue (native/core_tables.cc) directly at
    reference depth: 1M queued leases pushed, swept, and drained without
    any Python per-lease work — substantiating core_tables.cc's claim at
    the layer that makes it."""
    import ctypes

    from ray_tpu._native import get_lib, native_unavailable_reason

    reason = native_unavailable_reason()
    if reason:
        results.append(emit("envelope_native_sched", skipped=reason))
        return
    lib = get_lib()
    n = 50_000 if QUICK else n
    h = lib.rtpu_sched_open(1)
    ids = (ctypes.c_uint32 * 1)(0)        # resource id 0 == CPU
    amts = (ctypes.c_double * 1)(1.0)
    caps = (ctypes.c_double * 1)(float(n))
    lib.rtpu_sched_node_upsert(h, 1, ids, caps, caps, 1)
    t0 = time.perf_counter()
    for req in range(1, n + 1):
        lib.rtpu_sched_queue_push(h, req, ids, amts, 1, 0, 0)
    t_push = time.perf_counter() - t0
    pending = lib.rtpu_sched_pending(h)
    assert pending == n, (pending, n)
    batch = 4096
    out_req = (ctypes.c_uint64 * batch)()
    out_node = (ctypes.c_uint64 * batch)()
    granted = 0
    t0 = time.perf_counter()
    while True:
        got = lib.rtpu_sched_pump(h, out_req, out_node, batch)
        if not got:
            break
        granted += got
    t_drain = time.perf_counter() - t0
    lib.rtpu_sched_close(h)
    assert granted == n, (granted, n)
    results.append(emit(
        "envelope_native_sched", depth=n,
        push_per_s=n / t_push, grant_per_s=n / t_drain))


# ---------------------------------------------------------------- inflight
def bench_inflight(results, n=5_000, width=8):
    """n simultaneously in-flight (sleeping) invocations across `width`
    async actors (ref: many_tasks — 10k concurrent cluster-wide on 64
    nodes; one host multiplexes them onto async actor loops)."""
    import ray_tpu as ray

    n = 500 if QUICK else n

    @ray.remote
    class Sleeper:
        async def snooze(self, sec):
            import asyncio
            await asyncio.sleep(sec)
            return True

    actors = [Sleeper.options(num_cpus=0,
                              max_concurrency=(n // width) + 1).remote()
              for _ in range(width)]
    ray.get([a.snooze.remote(0) for a in actors])
    sleep_s = 15.0 if not QUICK else 3.0
    t0 = time.perf_counter()
    refs = [actors[i % width].snooze.remote(sleep_s) for i in range(n)]
    t_submit = time.perf_counter() - t0
    # all n must be unfinished (in flight) at once: if submission took
    # longer than the sleep, the early ones already completed.
    concurrent_ok = t_submit < sleep_s
    ray.get(refs)
    t_total = time.perf_counter() - t0
    results.append(emit(
        "envelope_inflight_tasks", depth=n,
        submit_s=t_submit, total_s=t_total,
        all_concurrent=bool(concurrent_ok)))


# ---------------------------------------------------------------- actors
def bench_actors(results, n=1_000):
    """n live actors at once (ref: many_actors — 40k cluster-wide).

    Runs in the SHARED session again (the r4 own-session isolation —
    9818ad7 — is gone): the task-event flusher is now bounded
    (core_worker._TASK_EVENT_FLUSH_MAX chunks) and actor registration
    is one pipelined async GCS hop, so the ~100k task-event backlog the
    earlier families leave can no longer starve creations. First-contact
    pings retry per actor (a creation still queued behind 900 others may
    exceed one ping's internal alive-wait without being dead)."""
    import ray_tpu as ray

    n = 50 if QUICK else n

    @ray.remote(num_cpus=0)
    class Cell:
        def __init__(self):
            self.v = 0

        def ping(self):
            self.v += 1
            return self.v

    t0 = time.perf_counter()
    actors = [Cell.remote() for _ in range(n)]
    alive = [False] * n
    deadline = time.monotonic() + 1200
    while not all(alive) and time.monotonic() < deadline:
        for i, a in enumerate(actors):
            if not alive[i]:
                try:
                    assert ray.get(a.ping.remote(), timeout=180) == 1
                    alive[i] = True
                except Exception:
                    pass
    assert all(alive), f"{alive.count(False)} actors never came up"
    t_up = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = ray.get([a.ping.remote() for a in actors], timeout=600)
    t_ping = time.perf_counter() - t0
    assert out == [2] * n
    for a in actors:
        ray.kill(a)
    results.append(emit(
        "envelope_many_actors", depth=n,
        create_and_first_ping_s=t_up, actors_per_s=n / t_up,
        ping_all_per_s=n / t_ping))


# -------------------------------------------------------------- gang restart
def bench_gang_restart(results):
    """SURVEY §7.4 fast gang restart, measured: a 2-worker gang loses a
    rank mid-run; report detect->restore->next-step wall time, plus the
    cold vs post-restart compile time of the jitted train step (the
    persistent XLA compilation cache makes the restart recompile warm —
    _private/device_plane.py enable_compilation_cache)."""
    import shutil
    import tempfile

    import ray_tpu as ray
    from ray_tpu.train import (
        FailureConfig, RunConfig, ScalingConfig, Trainer)

    cache_dir = tempfile.mkdtemp(prefix="envelope_ccache_")
    # trace lives OUTSIDE cache_dir: the cache_added entry counts must
    # see only jax-written cache files
    trace_dir = tempfile.mkdtemp(prefix="envelope_gangtrace_")
    trace = os.path.join(trace_dir, "trace.jsonl")
    # a fresh directory so the entry counts are this run's; workers
    # inherit the variable and jax reads it itself
    # (device_plane.enable_compilation_cache sets no directory then)
    prev_cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    ray.init(num_cpus=4)
    try:
        def train_fn(config):
            import json as _json
            import time as _time

            import jax
            import jax.numpy as jnp

            from ray_tpu import train

            ctx = train.get_context()
            trace_path = config["trace"]

            def log(**kw):
                with open(trace_path, "a") as f:
                    f.write(_json.dumps(kw) + "\n")

            start = 0
            ckpt = train.get_checkpoint()
            if ckpt is not None:
                with open(os.path.join(ckpt.path, "state.json")) as f:
                    start = _json.load(f)["step"]

            @jax.jit
            def step_fn(w, x):
                # big enough that the cold XLA compile clears the
                # persistent cache's 0.2 s floor on an idle host too
                # (device_plane.enable_compilation_cache)
                for i in range(64):
                    x = jnp.tanh(x @ w) + jax.nn.gelu(x) * (0.1 * i)
                return jax.nn.softmax(x, axis=-1)

            w = jnp.eye(512) * 0.5
            x = jnp.ones((64, 512))
            cache_dir = config["cache_dir"]
            before = len(os.listdir(cache_dir))
            t0 = _time.perf_counter()
            step_fn(w, x).block_until_ready()
            log(rank=ctx.rank, event="compiled", resumed_from=start,
                compile_s=_time.perf_counter() - t0,
                cache_added=len(os.listdir(cache_dir)) - before,
                t=_time.time())
            for step in range(start + 1, 10):
                if ctx.rank == 1 and ckpt is None and step == 3:
                    log(rank=1, event="death", t=_time.time())
                    os._exit(1)
                step_fn(w, x).block_until_ready()
                if ctx.rank == 0:
                    d = tempfile.mkdtemp()
                    with open(os.path.join(d, "state.json"), "w") as f:
                        _json.dump({"step": step}, f)
                    train.report({"step": step},
                                 train.Checkpoint(d))
                log(rank=ctx.rank, event="step", step=step,
                    resumed=start > 0, t=_time.time())
                _time.sleep(0.25)

        run_dir = tempfile.mkdtemp(prefix="envelope_gang_")
        result = Trainer(
            train_fn, train_loop_config={"trace": trace, "cache_dir": cache_dir},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(
                name="gang", storage_path=run_dir,
                failure_config=FailureConfig(max_failures=2)),
        ).fit()
        assert result.error is None, result.error
        events = [json.loads(l) for l in open(trace)]
        deaths = [e["t"] for e in events if e["event"] == "death"]
        death_t = max(deaths)
        after = [e for e in events
                 if e["event"] == "step" and e.get("resumed")]
        first_step_after = min(e["t"] for e in after)
        compiles = [e for e in events if e["event"] == "compiled"]
        cold = max(e["compile_s"] for e in compiles
                   if e["resumed_from"] == 0)
        warm = min(e["compile_s"] for e in compiles
                   if e["resumed_from"] > 0)
        # decisive cache evidence: the restarted incarnation's compile
        # must come from the persistent cache (zero NEW entries written)
        warm_added = sum(e["cache_added"] for e in compiles
                         if e["resumed_from"] > 0)
        cold_added = sum(e["cache_added"] for e in compiles
                         if e["resumed_from"] == 0)
        results.append(emit(
            "envelope_gang_restart",
            restart_to_next_step_s=first_step_after - death_t,
            cold_compile_s=cold, warm_compile_s=warm,
            cold_cache_entries_written=cold_added,
            restart_compile_cache_hit=bool(warm_added == 0
                                           and cold_added > 0),
            restarts=len(deaths)))
    finally:
        if prev_cache_dir is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = prev_cache_dir
        ray.shutdown()
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)


# ------------------------------------------------------------ train goodput
def bench_train_goodput(results):
    """Training goodput plane, measured: a short sharded fit on the tiny
    Llama config, recorded as the MFU / tok-per-chip baseline with the
    ledger's phase breakdown — so a step-time or goodput regression
    shows up as a number moving, not a vibe. Peak flops is pinned to a
    nominal 1e12/chip so recorded MFU values compare across hosts."""
    import dataclasses
    import shutil
    import tempfile

    import ray_tpu as ray
    from ray_tpu.train import RunConfig, ScalingConfig, Trainer
    from ray_tpu.util import state as state_api

    steps = 4 if QUICK else 8
    ray.init(num_cpus=4, _system_config={
        "train_peak_flops_per_chip": 1e12,
        "metrics_report_interval_ms": 300,
    })
    run_dir = tempfile.mkdtemp(prefix="envelope_goodput_")
    try:
        def train_fn(config):
            import jax
            import jax.numpy as jnp
            import optax

            from ray_tpu import train
            from ray_tpu.models import (
                LLAMA_CONFIGS, init_params, lm_loss, param_logical_axes)
            from ray_tpu.parallel import MeshSpec, build_mesh
            from ray_tpu.train import (
                estimate_flops_per_token, make_train_step)

            cfg = LLAMA_CONFIGS["tiny"]
            mesh = build_mesh(MeshSpec(dp=1, fsdp=1, tp=1),
                              jax.devices("cpu")[:1])
            init_fn, step_fn, place_batch = make_train_step(
                lambda p, b: lm_loss(p, b, cfg, mesh=mesh),
                optax.adamw(1e-3), mesh, param_logical_axes(cfg),
                model_flops_per_token=estimate_flops_per_token(
                    cfg.n_params()))
            st = init_fn(init_params(jax.random.PRNGKey(0), cfg))
            key = jax.random.PRNGKey(1)
            for _ in range(config["steps"]):
                with train.phase("data_wait"):
                    key, sub = jax.random.split(key)
                    tokens = jax.random.randint(
                        sub, (4, 32), 0, cfg.vocab, jnp.int32)
                batch = place_batch({"tokens": tokens})
                st, metrics = step_fn(st, batch)
                train.report({"loss": float(metrics["loss"])})

        t0 = time.perf_counter()
        result = Trainer(
            train_fn, train_loop_config={"steps": steps},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="goodput",
                                 storage_path=run_dir),
        ).fit()
        wall = time.perf_counter() - t0
        assert result.error is None, result.error
        deadline = time.time() + 20
        job = None
        while time.time() < deadline:
            jobs = state_api.train_status(job="goodput").get("jobs", [])
            jobs = [dataclasses.asdict(j) if dataclasses.is_dataclass(j)
                    else j for j in jobs]
            if jobs and jobs[0]["steps"] >= steps - 1:
                job = jobs[0]
                break
            time.sleep(0.25)
        assert job is not None, "goodput ledger never folded"
        badput = {k: round(v, 4) for k, v in sorted(
            job["badput_s"].items(), key=lambda kv: -kv[1])}
        recent = [r for r in job["recent"] if not r.get("rework")]
        step_walls = sorted(r["wall_s"] for r in recent)
        results.append(emit(
            "envelope_train_goodput",
            steps=job["steps"], fit_wall_s=wall,
            goodput_fraction=round(job["goodput_fraction"], 4),
            attributed_fraction=round(job["attributed_fraction"], 4),
            mfu=round(job["mfu"], 6),
            tok_per_s_per_chip=round(job["tok_per_s_per_chip"], 1),
            compile_cold=job["compile_count"],
            compile_cache_hit=job["cache_hit_count"],
            recompiles=job["recompile_count"],
            productive_s=round(job["productive_s"], 4),
            badput_s=badput,
            step_wall_p50_s=step_walls[len(step_walls) // 2]
            if step_walls else None,
            step_wall_max_s=step_walls[-1] if step_walls else None))
    finally:
        ray.shutdown()
        shutil.rmtree(run_dir, ignore_errors=True)


# ---------------------------------------------------------------- broadcast
def bench_broadcast(results, size_gb=1.0, nodes=4):
    """One size_gb object broadcast to every node of a multi-node
    fake cluster (ref: broadcast to 50+ nodes, README.md:18). Each node
    has an isolated object store, so every pull is a real inter-store
    transfer over the node transport."""
    import numpy as np

    import ray_tpu as ray
    from ray_tpu.cluster_utils import Cluster

    if QUICK:
        size_gb = 0.05
    nbytes = int(size_gb * (1 << 30))
    cluster = Cluster(head_node_args={"num_cpus": 1,
                                     "object_store_memory": 3 * nbytes})
    try:
        for i in range(nodes - 1):
            cluster.add_node(num_cpus=1, resources={f"slot{i}": 1.0},
                             object_store_memory=3 * nbytes)
        cluster.connect()
        deadline = time.monotonic() + 60
        while len(ray.nodes()) < nodes:
            if time.monotonic() > deadline:
                raise TimeoutError(f"cluster stuck below {nodes} nodes")
            time.sleep(0.2)

        @ray.remote
        def touch(arr):
            # completion timestamp: the spread max-min across nodes is
            # the pipeline fill — with cut-through relay every node
            # finishes a small fixed lag behind the origin stream, so
            # the spread stays near zero regardless of fan-out depth
            # (store-and-forward trees pay a full object copy per hop)
            return int(arr[0]) + int(arr[-1]), time.time()

        data = np.empty(nbytes, dtype=np.uint8)
        data[0] = 1
        data[-1] = 1
        ref = ray.put(data)
        del data
        t0 = time.perf_counter()
        outs = ray.get([
            touch.options(resources={f"slot{i}": 1.0}).remote(ref)
            for i in range(nodes - 1)], timeout=600)
        t_bcast = time.perf_counter() - t0
        assert [o[0] for o in outs] == [2] * (nodes - 1)
        done_ts = [o[1] for o in outs]
        results.append(emit(
            "envelope_broadcast", object_gb=round(size_gb, 2), nodes=nodes,
            broadcast_s=t_bcast,
            broadcast_pipeline_fill_s=max(done_ts) - min(done_ts),
            aggregate_gb_per_s=(nodes - 1) * size_gb / t_bcast))
    finally:
        ray.shutdown()
        cluster.shutdown()


# ---------------------------------------------------------------- getmany
def bench_getmany(results, n=10_000):
    """One ray.get over n store objects (ref: README.md:29, 10k+)."""
    import ray_tpu as ray

    n = 1_000 if QUICK else n
    payload = b"y" * 2048  # store-resident, not inline
    t0 = time.perf_counter()
    refs = [ray.put(payload) for _ in range(n)]
    t_put = time.perf_counter() - t0
    t0 = time.perf_counter()
    vals = ray.get(refs, timeout=600)
    t_get = time.perf_counter() - t0
    assert len(vals) == n and vals[0] == payload
    results.append(emit(
        "envelope_get_many", depth=n,
        put_per_s=n / t_put, get_per_s=n / t_get))


# ---------------------------------------------------------------- bigobj
def bench_bigobj(results, size_gb=30.0):
    """A single multi-GiB numpy object round-trip (ref: README.md:31,
    100 GiB on a 256 GB box; 30 GiB here on a 125 GB box — the same
    fraction of host memory class, bounded by this host's ~0.25 GB/s
    fresh-page write bandwidth, not by the store design)."""
    import numpy as np

    import ray_tpu as ray

    if QUICK:
        size_gb = 0.25
    elif MODERATE:
        size_gb = 10.0
    nbytes = int(size_gb * (1 << 30))
    # np.empty: untouched pages read as the shared zero page, so setup
    # doesn't pay a full-size write on bandwidth-poor hosts — the put
    # itself is the measured full-size write
    data = np.empty(nbytes, dtype=np.uint8)
    data[0] = 7
    data[-1] = 9
    t0 = time.perf_counter()
    ref = ray.put(data)
    t_put = time.perf_counter() - t0
    del data
    gc.collect()
    t0 = time.perf_counter()
    out = ray.get(ref)
    t_get = time.perf_counter() - t0
    assert out.nbytes == nbytes and out[0] == 7 and out[-1] == 9
    del out
    results.append(emit(
        "envelope_big_object", object_gb=size_gb,
        put_gb_per_s=size_gb / t_put, get_gb_per_s=size_gb / t_get))


# ---------------------------------------------------------------- spill
def bench_spill(results, total_gb=12.0, obj_gb=1.0, store_gb=4.0):
    """Objects exceeding the store's capacity: puts force spill-to-disk,
    gets restore lazily (ref: README.md's 100 GiB row is only reachable
    through spilling on smaller stores; object_store.py spill/restore).
    Own session: the store cap IS the experiment."""
    import numpy as np

    import ray_tpu as ray

    if QUICK:
        total_gb, obj_gb, store_gb = 1.0, 0.25, 0.5
    elif MODERATE:
        total_gb = 6.0
    n = int(total_gb / obj_gb)
    nbytes = int(obj_gb * (1 << 30))
    ray.init(num_cpus=2, object_store_memory=int(store_gb * (1 << 30)))
    try:
        # per-stage I/O counters (pure spill-write / restore-read time,
        # excluding admission waits): puts and gets run in THIS process,
        # so the driver's own store counters cover the whole run
        from ray_tpu._private.object_store import IO_STATS

        s0 = dict(IO_STATS)
        t0 = time.perf_counter()
        refs = []
        for i in range(n):
            a = np.empty(nbytes, dtype=np.uint8)
            a[0], a[-1] = i % 251, (i * 7) % 251
            refs.append(ray.put(a))
            del a
        t_put = time.perf_counter() - t0
        s1 = dict(IO_STATS)
        gc.collect()
        t0 = time.perf_counter()
        ok = 0
        for i, r in enumerate(refs):
            out = ray.get(r)
            assert out[0] == i % 251 and out[-1] == (i * 7) % 251
            ok += 1
            del out
            gc.collect()
        t_get = time.perf_counter() - t0
        s2 = dict(IO_STATS)

        def stage_rate(a, b, kind):
            nbytes_moved = b[kind + "_bytes"] - a[kind + "_bytes"]
            secs = b[kind + "_s"] - a[kind + "_s"]
            return (nbytes_moved / (1 << 30)) / secs if secs > 0 else 0.0

        results.append(emit(
            "envelope_spill", total_gb=total_gb, store_gb=store_gb,
            objects=n, put_gb_per_s=total_gb / t_put,
            restore_get_gb_per_s=total_gb / t_get,
            spill_write_io_gb_per_s=stage_rate(s0, s2, "spill"),
            restore_read_io_gb_per_s=stage_rate(s1, s2, "restore")))
    finally:
        ray.shutdown()


# ---------------------------------------------------------------- syncer
def bench_syncer(results, nodes=64, reports=8000):
    """Where the hub resource-sync ceiling sits: sustained
    report_resources/s through ONE GCS loop with `nodes` subscriber
    connections each receiving the fan-out — the O(N^2) path gossip
    mode replaces (ray_tpu/_private/syncer.py)."""
    import asyncio
    import tempfile

    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.rpc import RpcClient

    if QUICK:
        nodes, reports = 8, 500

    async def go():
        tmp = tempfile.mkdtemp(prefix="rtpu_sync_bench_")
        sock = f"{tmp}/gcs.sock"
        gcs = GcsServer(sock)
        await gcs.start()
        clients = []
        node_ids = []
        for i in range(nodes):
            c = RpcClient(sock)
            await c.connect()
            nid = NodeID.from_random()
            await c.call("register_node", {
                "node_id": nid, "address": f"fake-{i}",
                "resources_total": {"CPU": 8.0},
                "resources_available": {"CPU": 8.0}})
            # every node subscribes: each report fans out to all N
            await c.call("subscribe", {"channels": ["resources"]})
            clients.append(c)
            node_ids.append(nid)
        seqs = [0] * nodes
        t0 = time.perf_counter()

        async def one(i, k):
            seqs[i] += 1
            await clients[i].call("report_resources", {
                "node_id": node_ids[i],
                "available": {"CPU": float(k % 8)},
                "seq": seqs[i]})

        # bounded concurrency so the measurement is throughput, not
        # queue depth
        sem = asyncio.Semaphore(64)

        async def guarded(i, k):
            async with sem:
                await one(i, k)

        await asyncio.gather(*(guarded(k % nodes, k)
                               for k in range(reports)))
        dt = time.perf_counter() - t0
        for c in clients:
            await c.close()
        await gcs.stop()
        return reports / dt

    loop = asyncio.new_event_loop()
    try:
        rate = loop.run_until_complete(go())
    finally:
        loop.close()
    results.append(emit(
        "envelope_hub_sync", nodes=nodes, reports=reports,
        hub_reports_per_s=rate,
        # each report pushes to `nodes` subscribers: the loop moves
        # rate*nodes messages/s at saturation
        hub_fanout_msgs_per_s=rate * nodes))


# --------------------------------------------------------------- shuffle
def bench_shuffle(results, blocks=16, rows_per_block=50_000,
                  payload_width=16):
    """Push-based shuffle exchange (data/shuffle.py): rows/s for sort /
    repartition / random_shuffle at N blocks x M rows, plus the largest
    payload any single driver-side get() materialized during the
    exchange — the O(one block) driver-residency envelope. Own session:
    the dataset should dwarf inline thresholds but fit the store."""
    import numpy as np

    import ray_tpu as ray
    import ray_tpu.data as rdata
    from ray_tpu.util.metrics import snapshot_local

    if QUICK:
        blocks, rows_per_block = 4, 4_000
    elif MODERATE:
        blocks, rows_per_block = 8, 20_000
    n = blocks * rows_per_block

    def make_ds():
        def widen(b):
            ids = np.asarray(b["id"])
            return {"id": ids,
                    "key": (ids * 2654435761) % 1_000_003,
                    "payload": np.tile(ids.astype(np.float64),
                                       (payload_width, 1)).T.copy()}

        return rdata.range(n, parallelism=blocks).map_batches(widen)

    ops = {
        "sort": lambda ds: ds.sort("key"),
        "repartition": lambda ds: ds.repartition(max(2, blocks // 2)),
        "random_shuffle": lambda ds: ds.random_shuffle(seed=7),
    }
    ray.init(num_cpus=4)
    try:
        import cloudpickle

        for op, build in ops.items():
            peak = {"v": 0}
            orig_get = ray.get

            def metered(refs, **kwargs):
                out = orig_get(refs, **kwargs)
                for v in (out if isinstance(out, list) else [out]):
                    try:
                        peak["v"] = max(peak["v"],
                                        len(cloudpickle.dumps(v)))
                    except Exception:
                        pass
                return out

            ray.get = metered
            try:
                t0 = time.perf_counter()
                out_refs = list(build(make_ds()).iter_block_refs())
                dt = time.perf_counter() - t0
            finally:
                ray.get = orig_get
            snap = snapshot_local("data_shuffle")
            results.append(emit(
                "envelope_shuffle", op=op, blocks=blocks, rows=n,
                s=round(dt, 3), rows_per_s=int(n / dt),
                out_blocks=len(out_refs),
                peak_driver_get_bytes=peak["v"],
                bytes_pushed=int(snap.get(
                    f"data_shuffle_bytes_pushed_total{{op={op}}}", 0)),
                driver_rss_mb=_rss_mb()))
    finally:
        ray.shutdown()


# ---------------------------------------------------------------- tail
def _pctl(samples, q):
    xs = sorted(samples)
    return xs[min(len(xs) - 1, int(q * (len(xs) - 1)))]


def bench_tail(results):
    """Tail-latency envelope (The Tail at Scale): task and serve
    p50/p99/p999 with one deterministically slow node / periodically
    slow replica, hedging off vs on. The before/after pair is the
    record that speculative re-execution buys its p99 claim."""
    import ray_tpu as ray
    from ray_tpu._private.config import global_config
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.metrics import snapshot_local

    waves = 8 if QUICK else 25
    slow_s = 1.0

    def run_tasks(speculate: bool):
        # driver-only head: every task leases remotely; SPREAD straddles
        # the fast and straggler nodes, so roughly half of each 4-wide
        # wave lands slow — the tail the hedges must erase
        from ray_tpu.util.scheduling_strategies import (
            SpreadSchedulingStrategy)

        global_config().apply_overrides({
            "prestart_workers": False,
            "task_speculation_enabled": speculate,
            "task_hedge_min_delay_s": 0.1,
            "task_hedge_ema_factor": 3.0,
            "task_watchdog_interval_s": 0.25,
            "task_stall_threshold_s": 0.35,
        })
        cluster = Cluster(head_node_args={"num_cpus": 0})
        try:
            cluster.add_node(num_cpus=2)          # the healthy node
            slow = cluster.add_node(num_cpus=2)
            os.environ["RAY_TPU_FAILPOINTS"] = (
                f"worker.task.run@{slow.node_id.hex()}=slow:{slow_s}")
            cluster.connect()

            @ray.remote(idempotent=True,
                        scheduling_strategy=SpreadSchedulingStrategy())
            def unit():
                time.sleep(0.02)
                return 1

            ray.get([unit.remote() for _ in range(4)], timeout=120)
            lat = []
            for _ in range(waves):
                t0 = time.perf_counter()
                refs = [unit.remote() for _ in range(4)]
                for r in refs:
                    ray.get(r, timeout=120)
                    lat.append(time.perf_counter() - t0)
            return lat
        finally:
            os.environ.pop("RAY_TPU_FAILPOINTS", None)
            cluster.shutdown()

    snap0 = snapshot_local("task_hedge")
    lat_before = run_tasks(False)
    lat_after = run_tasks(True)
    snap1 = snapshot_local("task_hedge")
    delta = {k: snap1.get(k, 0) - snap0.get(k, 0)
             for k in ("task_hedges_launched", "task_hedges_won",
                       "task_hedge_duplicate_publishes")}
    n = 4 * waves
    p99_speedup = _pctl(lat_before, 0.99) / max(1e-9,
                                                _pctl(lat_after, 0.99))
    assert delta["task_hedge_duplicate_publishes"] == 0, \
        "a hedged task sealed its output twice"
    results.append(emit(
        "envelope_tail_tasks", n=n, slow_node_penalty_s=slow_s,
        p50_before_ms=_pctl(lat_before, 0.5) * 1e3,
        p99_before_ms=_pctl(lat_before, 0.99) * 1e3,
        p999_before_ms=_pctl(lat_before, 0.999) * 1e3,
        p50_after_ms=_pctl(lat_after, 0.5) * 1e3,
        p99_after_ms=_pctl(lat_after, 0.99) * 1e3,
        p999_after_ms=_pctl(lat_after, 0.999) * 1e3,
        p99_speedup=p99_speedup,
        hedges_launched=delta["task_hedges_launched"],
        hedges_won=delta["task_hedges_won"],
        hedge_rate=round(delta["task_hedges_launched"] / n, 3),
        duplicate_publishes=delta["task_hedge_duplicate_publishes"]))

    # ---- serve: 2 replicas, every 10th request on a replica stalls ----
    n_serve = 40 if QUICK else 150
    budget = 0.25

    def run_serve(hedge: bool):
        # the hedge quantile must sit BELOW the tail fraction: with every
        # 10th request slow, a p95 trigger delay IS the straggle latency
        # and the backup always fires too late; p80 sits in the fast band
        ray.init(num_cpus=4, _system_config={
            "serve_hedge_quantile": 0.8 if hedge else 0.0,
            "serve_hedge_budget": budget,
            "serve_hedge_min_samples": 8,
        })
        try:
            from ray_tpu import serve

            @serve.deployment(num_replicas=2)
            class Unit:
                def __init__(self):
                    self.i = 0

                def __call__(self, x):
                    self.i += 1
                    if self.i % 10 == 0:
                        time.sleep(0.4)  # the periodic straggle
                    return x

            handle = serve.run(Unit.bind())
            for i in range(16):  # warm replicas + latency profile
                ray.get(handle.remote(i), timeout=60)
            lat = []
            for i in range(n_serve):
                t0 = time.perf_counter()
                assert ray.get(handle.remote(i), timeout=60) == i
                lat.append(time.perf_counter() - t0)
            return lat, handle._requests_total, handle._hedges_launched
        finally:
            serve.shutdown()
            ray.shutdown()

    lat_before, _, _ = run_serve(False)
    lat_after, total, hedged = run_serve(True)
    assert hedged <= budget * total + 1, \
        f"hedge budget exceeded: {hedged}/{total}"
    results.append(emit(
        "envelope_tail_serve", n=n_serve, slow_every=10,
        replica_penalty_s=0.4,
        p50_before_ms=_pctl(lat_before, 0.5) * 1e3,
        p99_before_ms=_pctl(lat_before, 0.99) * 1e3,
        p999_before_ms=_pctl(lat_before, 0.999) * 1e3,
        p50_after_ms=_pctl(lat_after, 0.5) * 1e3,
        p99_after_ms=_pctl(lat_after, 0.99) * 1e3,
        p999_after_ms=_pctl(lat_after, 0.999) * 1e3,
        p99_speedup=_pctl(lat_before, 0.99) / max(
            1e-9, _pctl(lat_after, 0.99)),
        hedge_rate=round(hedged / max(1, total), 3),
        hedge_budget=budget))


# ------------------------------------------------------------ serve_prefix
def bench_serve_prefix(results):
    """Fleet KV plane envelope (llm/serve.py + serve/kv_router.py):

      * prefix-affinity routing — 2 monolithic replicas taking
        shared-prefix traffic, routing off vs on, cold vs warm TTFT.
        With affinity on, warm requests land on the replica whose
        prefix cache already holds the shared pages.
      * disaggregated prefill/decode — 1+1 pools: per-request handoff
        overhead vs the monolithic warm path, and decode TPOT with and
        without a concurrent long prefill (the interference the pool
        split exists to remove).
    """
    import ray_tpu as ray

    ecfg = {"max_num_seqs": 2, "max_seq_len": 256, "num_pages": 128,
            "page_size": 16, "enable_prefix_caching": True}
    shared = list(range(2, 130))          # 128-token shared prefix
    reps = 3 if QUICK else 8

    def _e2e(comp, prompt, max_tokens=2):
        t0 = time.perf_counter()
        out = ray.get(comp.remote({"prompt_ids": list(prompt),
                                   "temperature": 0.0,
                                   "max_tokens": max_tokens}),
                      timeout=600)
        dt = time.perf_counter() - t0
        assert len(out["choices"][0]["token_ids"]) == max_tokens, out
        return dt

    def run_affinity(enabled: bool):
        ray.init(num_cpus=4, _system_config={
            "serve_prefix_routing_enabled": enabled,
            "serve_prefix_summary_interval_s": 0.25,
        })
        try:
            from ray_tpu import serve
            from ray_tpu.llm.serve import build_llm_deployment

            app = build_llm_deployment("tiny", name="llm_aff",
                                       num_replicas=2,
                                       engine_config=ecfg)
            comp = serve.run(app).options(method_name="completions")
            cold = _e2e(comp, shared + [997])
            # summary gossip rides the controller's reconcile tick
            # (~2 s): wait for the summaries to actually exist before
            # measuring warm routing (with routing off none ever appear
            # — the deadline is the fixed warmup then)
            deadline = time.time() + 12
            while time.time() < deadline:
                dep = next(d for d in serve.status()
                           if d["name"] == "llm_aff")
                if dep.get("prefix_summaries", 0) > 0:
                    break
                time.sleep(0.5)
            warm = [_e2e(comp, shared + [1000 + i]) for i in range(reps)]
            return cold, warm
        finally:
            serve.shutdown()
            ray.shutdown()

    cold_off, warm_off = run_affinity(False)
    cold_on, warm_on = run_affinity(True)
    results.append(emit(
        "envelope_serve_prefix_affinity",
        prefix_tokens=len(shared), requests=reps,
        cold_ttft_off_ms=cold_off * 1e3,
        warm_ttft_off_mean_ms=sum(warm_off) / len(warm_off) * 1e3,
        warm_ttft_off_max_ms=max(warm_off) * 1e3,
        cold_ttft_on_ms=cold_on * 1e3,
        warm_ttft_on_mean_ms=sum(warm_on) / len(warm_on) * 1e3,
        warm_ttft_on_max_ms=max(warm_on) * 1e3,
        warm_mean_speedup=(sum(warm_off) / max(1e-9, sum(warm_on)))))

    # ---- disaggregated pools: handoff overhead + TPOT isolation ----
    ray.init(num_cpus=4, _system_config={
        "serve_prefix_summary_interval_s": 0.25,
    })
    try:
        from ray_tpu import serve
        from ray_tpu.llm.serve import build_llm_deployment

        app = build_llm_deployment("tiny", name="llm_pool",
                                   pools={"prefill": 1, "decode": 1},
                                   engine_config=ecfg)
        comp = serve.run(app).options(method_name="completions")
        _e2e(comp, shared + [1])              # warm both engines
        hand = [_e2e(comp, shared + [50 + i]) for i in range(reps)]

        # decode TPOT read from the serving engine's own
        # llm_tpot_seconds histogram ((finish - first_token)/(n-1),
        # recorded where the tokens are produced and tagged with the
        # pool). Client-side timings are useless at this model size:
        # a two-point e2e slope goes negative under transient queueing,
        # and inter-chunk stream gaps bottom out at the pull-RPC
        # latency once the decode queue buffers ahead of the client.
        from ray_tpu.serve.replica import _STREAM_END
        from ray_tpu.util import state as state_api

        def _tpot_hist(pool):
            s = c = 0.0
            for e in state_api.get_metrics("llm_tpot_seconds"):
                tags = e.get("tags") or {}
                if tags.get("pool") != pool:
                    continue
                if tags.get("__stat__") == "sum":
                    s += e.get("value", 0.0)
                elif tags.get("__stat__") == "count":
                    c += e.get("value", 0.0)
            return s, c

        # pure-prefill interferers: max_tokens=1 keeps them out of the
        # decode batch entirely (the degenerate first token finishes at
        # prefill), distinct long prompts defeat the prefix cache, and
        # several of them cover the whole measurement window
        def prefill_storm(base):
            # distinct pseudo-random 227-token prompts inside the tiny
            # model's 256-token vocab (distinctness defeats the cache)
            return [comp.remote({
                "prompt_ids": [(b * 7 + i * 3) % 251 + 1
                               for i in range(227)],
                "temperature": 0.0, "max_tokens": 1})
                    for b in range(base, base + 12)]

        def _quiesce(pool):
            # earlier requests' observations may still be sitting in a
            # replica's local registry (periodic ~2 s flusher): wait for
            # the histogram to hold still for a full flush period so the
            # next before/after delta contains exactly one observation
            s, c = _tpot_hist(pool)
            stable = time.time()
            while time.time() - stable < 2.5:
                time.sleep(0.25)
                s2, c2 = _tpot_hist(pool)
                if c2 != c:
                    s, c, stable = s2, c2, time.time()
            return s, c

        def stream_tpot(suffix, pool=None, storm_base=None):
            before = _quiesce(pool) if pool else (0.0, 0.0)
            ref, replica = comp.route({
                "prompt_ids": shared + [suffix], "temperature": 0.0,
                "max_tokens": 24, "stream": True})
            # the ref resolves once prefill (and, for pools, the KV
            # handoff) is done and the stream exists — firing the storm
            # here puts every measured decode step under interference
            sid = ray.get(ref, timeout=600)["__stream__"]
            storm_refs = prefill_storm(storm_base) \
                if storm_base is not None else []
            while True:
                chunk = ray.get(replica.next_chunk.remote(sid),
                                timeout=600)
                if chunk == _STREAM_END:
                    break
            if storm_refs:
                ray.get(storm_refs, timeout=600)
            if not pool:
                return 0.0      # warmup call: nothing to report
            # the replica-side metrics flusher is periodic (~2 s):
            # wait for this request's observation to land
            deadline = time.time() + 20
            while time.time() < deadline:
                s, c = _tpot_hist(pool)
                if c > before[1]:
                    return (s - before[0]) / (c - before[1]) * 1000.0
                time.sleep(0.25)
            raise AssertionError(
                f"llm_tpot_seconds{{pool={pool}}} never flushed")

        # shape warmup: run one throwaway stream WITH a storm so every
        # batch shape (decode-only and decode+chunked-prefill) is
        # compiled before anything is measured (its compile-stall-
        # inflated observation is fenced off by _quiesce)
        stream_tpot(290, storm_base=2000)
        base_tpot = stream_tpot(300, pool="decode")
        # long prefills run concurrently with the decode stream — the
        # pool split should keep decode TPOT flat
        under_tpot = stream_tpot(400, pool="decode", storm_base=3000)
    finally:
        serve.shutdown()
        ray.shutdown()

    # ---- monolithic control: same interference experiment on ONE
    # shared engine. The pooled run's residual slowdown is host CPU
    # contention between two engine processes; the mono run shows what
    # disaggregation removes — the long prefill's chunks interleaving
    # with decode steps inside the same engine loop.
    ray.init(num_cpus=4)
    try:
        from ray_tpu import serve
        from ray_tpu.llm.serve import build_llm_deployment

        app = build_llm_deployment("tiny", name="llm_mono",
                                   num_replicas=1, engine_config=ecfg)
        comp = serve.run(app).options(method_name="completions")
        _e2e(comp, shared + [1])

        # shape warmup (see the pooled block)
        stream_tpot(309, storm_base=4000)
        mono_base = stream_tpot(310, pool="mono")
        mono_under = stream_tpot(410, pool="mono", storm_base=5000)
    finally:
        serve.shutdown()
        ray.shutdown()

    warm_on_mean = sum(warm_on) / len(warm_on)
    pooled_x = under_tpot / max(1e-9, base_tpot)
    mono_x = mono_under / max(1e-9, mono_base)
    results.append(emit(
        "envelope_serve_prefix_pools",
        prefix_tokens=len(shared), requests=reps,
        handoff_e2e_mean_ms=sum(hand) / len(hand) * 1e3,
        handoff_e2e_max_ms=max(hand) * 1e3,
        mono_warm_e2e_mean_ms=warm_on_mean * 1e3,
        handoff_overhead_x=(sum(hand) / len(hand))
        / max(1e-9, warm_on_mean),
        decode_tpot_ms=base_tpot,
        decode_tpot_under_prefill_ms=under_tpot,
        tpot_interference_x=pooled_x,
        mono_tpot_ms=mono_base,
        mono_tpot_under_prefill_ms=mono_under,
        mono_interference_x=mono_x,
        isolation_gain_x=mono_x / max(1e-9, pooled_x)))


# ----------------------------------------------------------- serve_spec
def bench_serve_spec(results):
    """Speculative-decoding envelope (llm/spec_decode.py): generated
    tok/s and TPOT p99 for one serve replica under concurrent greedy
    loadgen, sequential decode vs draft/verify decode. Three regimes:

      * base    — no speculation (the sequential-decode baseline the
                  8b serve number has been pinned at),
      * spec    — drafter initialized from the SAME seed as the target
                  (the high-acceptance regime: k accepted tokens per
                  verify forward),
      * adverse — drafter from a different seed (rejection-heavy: the
                  floor, paying draft+verify for ~1 token/round).

    Acceptance ratios come from the engine's own SpecDecoder counters
    (handle stats — no flush lag); TPOT p99 interpolates the
    llm_tpot_seconds histogram buckets the replica exported."""
    import ray_tpu as ray

    ecfg = {"max_num_seqs": 2, "max_seq_len": 256, "num_pages": 128,
            "page_size": 16}
    gen = 24
    waves = 3 if QUICK else 6
    conc = 2                      # matches max_num_seqs: full batch
    # prompt mix: short / medium / long, distinct contents
    mix = [list(range(3, 11)),
           [(i * 5) % 251 + 1 for i in range(48)],
           [(i * 11) % 251 + 1 for i in range(96)]]

    def _tpot_p99_ms():
        from ray_tpu.util import state as state_api
        from ray_tpu.util.metrics import histogram_quantile

        deadline = time.time() + 20
        while time.time() < deadline:
            buckets = {}
            for e in state_api.get_metrics("llm_tpot_seconds"):
                tags = e.get("tags") or {}
                le = tags.get("le")
                if le is None:
                    continue
                bound = float(le)
                buckets[bound] = buckets.get(bound, 0.0) \
                    + e.get("value", 0.0)
            q = histogram_quantile(0.99, buckets.items())
            if q is not None:
                return q * 1000.0
            time.sleep(0.5)     # periodic replica-side flusher
        raise AssertionError("llm_tpot_seconds never flushed")

    def run_regime(name, speculation):
        ray.init(num_cpus=4)
        try:
            from ray_tpu import serve
            from ray_tpu.llm.serve import build_llm_deployment

            kwargs = {"engine_config": ecfg}
            if speculation:
                kwargs["speculation"] = speculation
            app = build_llm_deployment("tiny", name=name, **kwargs)
            comp = serve.run(app).options(method_name="completions")
            # shape warmup: prefill buckets + decode (+ verify) compiles
            for p in mix:
                ray.get(comp.remote({"prompt_ids": list(p),
                                     "temperature": 0.0,
                                     "max_tokens": 4}), timeout=600)
            t0 = time.perf_counter()
            toks = 0
            for w in range(waves):
                refs = [comp.remote({
                    "prompt_ids": list(mix[(w * conc + i) % len(mix)]),
                    "temperature": 0.0, "max_tokens": gen})
                    for i in range(conc)]
                for out in ray.get(refs, timeout=600):
                    toks += len(out["choices"][0]["token_ids"])
            wall = time.perf_counter() - t0
            stats = ray.get(
                serve.get_deployment_handle(name).options(
                    method_name="stats").remote(), timeout=60)
            p99 = _tpot_p99_ms()
            return toks / max(1e-9, wall), p99, stats.get("spec") or {}
        finally:
            serve.shutdown()
            ray.shutdown()

    base_tps, base_p99, _ = run_regime("llm_specbase", None)
    spec_tps, spec_p99, spec_stats = run_regime(
        "llm_spec", {"draft_config": "tiny", "num_draft_tokens": 3,
                     "draft_seed": 0})
    adv_tps, adv_p99, adv_stats = run_regime(
        "llm_specadv", {"draft_config": "tiny", "num_draft_tokens": 3,
                        "draft_seed": 1})
    total = waves * conc * gen
    results.append(emit(
        "envelope_serve_spec",
        requests=waves * conc, gen_tokens=total,
        base_tok_s=base_tps, base_tpot_p99_ms=base_p99,
        spec_tok_s=spec_tps, spec_tpot_p99_ms=spec_p99,
        spec_accept_ratio=round(
            spec_stats.get("acceptance_ratio", 0.0), 4),
        spec_accepted_tok_s=(
            spec_stats.get("accepted_tokens", 0)
            / max(1e-9, total / max(1e-9, spec_tps))),
        spec_speedup_x=spec_tps / max(1e-9, base_tps),
        adverse_tok_s=adv_tps, adverse_tpot_p99_ms=adv_p99,
        adverse_accept_ratio=round(
            adv_stats.get("acceptance_ratio", 0.0), 4),
        adverse_speedup_x=adv_tps / max(1e-9, base_tps)))


# ------------------------------------------------------------------ slo
def bench_slo(results):
    """SLO observability plane envelope (ray_tpu/slo.py + scripts/
    loadgen.py): open-loop multi-tenant load against a healthy toy
    deployment records per-tenant SLO attainment; then the same load
    against a failpoint-degraded deployment must trip the fast
    burn-rate alert as an ERROR cluster event, and the time-to-alert is
    the recorded number."""
    import ray_tpu as ray
    from ray_tpu import serve
    from ray_tpu.scripts.loadgen import TenantProfile, run_loadgen
    from ray_tpu.util import state

    duration = 6.0 if QUICK else 12.0
    slow_s = 0.6
    # failpoints ride the env var, not _system_config: replica actors run
    # in worker processes that read RAY_TPU_FAILPOINTS at spawn (same
    # idiom as bench_tail) — the driver-side config override never
    # reaches them. Scoped to the degraded deployment ONLY: every
    # SloSlow request eats the straggle, healthy SloUnit is untouched.
    os.environ["RAY_TPU_FAILPOINTS"] = (
        f"serve.replica.handle@SloSlow=slow:{slow_s}")
    ray.init(num_cpus=4, _system_config={
        # tight ticks so attainment/burn react within the bench window
        "metrics_report_interval_ms": 500,
        "slo_eval_interval_s": 0.5,
        "metrics_series_min_interval_s": 0.4,
        "slo_fast_burn_windows_s": "3,6",
        "slo_slow_burn_windows_s": "6,12",
    })
    try:
        @serve.deployment(num_replicas=2)
        class SloUnit:
            def __call__(self, payload):
                time.sleep(0.005)
                return {"ok": True}

        @serve.deployment
        class SloSlow:
            def __call__(self, payload):
                return {"ok": True}

        serve.run(SloUnit.bind())
        serve.run(SloSlow.bind())
        port = serve.start()
        url = f"http://127.0.0.1:{port}"

        # phase 1 — healthy: per-tenant attainment should hold
        report = run_loadgen(
            url, "SloUnit",
            [TenantProfile("acme", 8.0, prompt_mu=3.0),
             TenantProfile("free", 4.0, prompt_mu=3.0)],
            duration, seed=0, settle_s=2.0,
            slo_specs=[
                "acme-latency: latency_p95 < 300ms "
                "@ deployment=SloUnit,tenant=acme window=20s",
                "free-latency: latency_p95 < 300ms "
                "@ deployment=SloUnit,tenant=free window=20s",
                "slow-latency: latency_p99 < 200ms "
                "@ deployment=SloSlow window=20s",
            ])
        by_tenant = {
            t: {"requests": r["requests"], "errors": r["errors"],
                "p95_ms": (r["latency_s"]["p95"] or 0) * 1e3}
            for t, r in report["tenants"].items()}
        att = {s["name"]: s["attainment"]
               for s in (report["slo"] or {}).get("specs", [])}
        # the monitor needs two flushed samples of a series before a
        # windowed delta exists; if the report raced the first tick,
        # re-poll — the 20s spec window keeps attainment live well past
        # the end of traffic
        deadline = time.time() + 10.0
        while att.get("acme-latency") is None and time.time() < deadline:
            time.sleep(0.5)
            att = {s["name"]: s["attainment"]
                   for s in state.slo_status().get("specs", [])}
        assert att.get("acme-latency") is not None, \
            f"no per-tenant attainment recorded: {att}"

        # phase 2 — degraded: every SloSlow request eats slow_s, so the
        # p99<200ms budget burns at ~100x and the fast alert must fire
        t_inject = time.time()
        run_loadgen(
            url, "SloSlow", [TenantProfile("acme", 6.0, prompt_mu=3.0)],
            duration, seed=1, settle_s=3.0)
        alerts = [e for e in state.list_cluster_events(source="slo")
                  if e.get("kind") == "fast_burn"
                  and (e.get("timestamp") or 0) >= t_inject]
        assert alerts, "fast-burn alert never fired under injected slow"
        time_to_alert = alerts[0]["timestamp"] - t_inject
        status = state.slo_status()
        slow_spec = next(s for s in status["specs"]
                         if s["name"] == "slow-latency")
        results.append(emit(
            "envelope_slo", duration_s=duration,
            tenants=by_tenant,
            attainment={k: (round(v, 5) if v is not None else None)
                        for k, v in att.items()},
            injected_slow_s=slow_s,
            fast_burn_fired=True,
            time_to_alert_s=round(time_to_alert, 2),
            degraded_attainment=slow_spec.get("attainment"),
            degraded_alert=slow_spec.get("alert")))
    finally:
        os.environ.pop("RAY_TPU_FAILPOINTS", None)
        try:
            serve.shutdown()
        finally:
            ray.shutdown()


def bench_submit(results):
    """Driver submit-path stage breakdown + always-on profiler overhead
    (ROADMAP item 2: "profile the 6k/s submit path" — this is the
    baseline that work is measured against). Two sessions, NOT
    in-session: profiling off (per-stage sums from submit_stage_seconds,
    checked against the measured submit wall) and always-on sampling at
    1 Hz (the throughput delta is the cost of leaving it on)."""
    import ray_tpu as ray

    n = 2_000 if QUICK else (20_000 if MODERATE else 50_000)

    def _stage_sums(snap, base):
        """{stage: seconds} deltas from two snapshot_local() reads of
        the submit_stage_seconds histogram (__stat__=sum entries)."""
        out = {}
        for key, v in snap.items():
            if "__stat__=sum" not in key or "{" not in key:
                continue
            tags = dict(p.split("=", 1)
                        for p in key[key.index("{") + 1:-1].split(","))
            stage = tags.get("stage")
            if stage:
                out[stage] = v - base.get(key, 0.0)
        return out

    def _run(sample_hz):
        from ray_tpu.util import metrics

        ray.init(num_cpus=4, _system_config={
            "profiling_sample_hz": sample_hz})
        try:
            @ray.remote
            def nop():
                return None

            # warmup: export the function, spin up workers, fill caches
            ray.get([nop.remote() for _ in range(200)])
            base = metrics.snapshot_local("submit_stage_seconds")
            t0 = time.perf_counter()
            refs = [nop.remote() for _ in range(n)]
            t_submit = time.perf_counter() - t0
            snap = metrics.snapshot_local("submit_stage_seconds")
            for i in range(0, n, 10_000):
                ray.get(refs[i:i + 10_000])
            return n / t_submit, t_submit, _stage_sums(snap, base)
        finally:
            ray.shutdown()

    tput_off, wall_off, sums = _run(0.0)
    tput_on, _, _ = _run(1.0)
    # the sync stages partition submit_task exactly; async/side stages
    # (lease_acquire, lane_push, lane_queue) report alongside
    sync = [s for s in sums
            if s not in ("total", "lease_acquire", "lane_push",
                         "lane_queue")]
    stage_sum = sum(sums[s] for s in sync)
    total = sums.get("total", 0.0)
    overhead_pct = (100.0 * (tput_off - tput_on) / tput_off
                    if tput_off else 0.0)
    results.append(emit(
        "envelope_submit", depth=n,
        submit_per_s=tput_off,
        stage_us={s: round(v / n * 1e6, 3) for s, v in sums.items()},
        stage_sum_vs_total=(round(stage_sum / total, 3) if total else None),
        stage_total_vs_wall=(round(total / wall_off, 3)
                             if wall_off else None),
        sampling_on_submit_per_s=tput_on,
        sampling_overhead_pct=round(overhead_pct, 2)))


# in-session families in dict order = default run order: "actors" LAST
# among them so its creations contend with the task-event backlog the
# earlier families leave (the regime the r4 bench dodged)
ALL = {
    "queued": bench_queued,
    "sched": bench_sched,
    "syncer": bench_syncer,
    "inflight": bench_inflight,
    "getmany": bench_getmany,
    "bigobj": bench_bigobj,
    "actors": bench_actors,
    "broadcast": bench_broadcast,
    "gang": bench_gang_restart,
    "train_goodput": bench_train_goodput,
    "spill": bench_spill,
    "shuffle": bench_shuffle,
    "tail": bench_tail,
    "serve_prefix": bench_serve_prefix,
    "serve_spec": bench_serve_spec,
    "slo": bench_slo,
    "submit": bench_submit,
}

# families that run inside a ray.init'd single-node session; "actors"
# runs LAST so its creations contend with the full task-event backlog
# the earlier families leave — the regime the r4 bench dodged
_IN_SESSION = {"queued", "inflight", "getmany", "bigobj", "actors"}


def main():
    names = FAMILIES or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        raise SystemExit(f"unknown families: {unknown} (have {list(ALL)})")
    results = []
    t0 = time.time()
    in_session = [n for n in names if n in _IN_SESSION]
    if in_session:
        import ray_tpu as ray
        store = (2 << 30)
        if "bigobj" in in_session and not QUICK:
            store = (14 << 30) if MODERATE else (36 << 30)
        ray.init(num_cpus=4, object_store_memory=store)
        try:
            for name in in_session:
                ALL[name](results)
        finally:
            ray.shutdown()
    for name in names:
        if name not in _IN_SESSION:
            ALL[name](results)
    print(json.dumps({
        "suite": "envelope",
        "elapsed_s": round(time.time() - t0, 1),
        "results": {r["bench"]: {k: v for k, v in r.items() if k != "bench"}
                    for r in results},
    }), flush=True)


if __name__ == "__main__":
    main()
