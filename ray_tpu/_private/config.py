"""Global flag table, env-var overridable.

TPU-native analog of the reference RAY_CONFIG system (ref:
src/ray/common/ray_config_def.h — 224 flags, each overridable via a RAY_<name>
env var and via the driver's _system_config). We keep the same contract:
 * every flag has a typed default,
 * `RAY_TPU_<NAME>` env vars override defaults at process start,
 * a driver-supplied dict overrides both and is propagated to workers through
   the control plane (workers call `apply_overrides` on connect).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any, Dict

_ENV_PREFIX = "RAY_TPU_"

# session roots live here (node session dirs, worker logs); single source
# of truth for every module that derives session paths
TEMP_ROOT = "/tmp/ray_tpu"


def session_log_dir(session_name: str) -> str:
    return os.path.join(TEMP_ROOT, session_name, "logs")


def _coerce(value: str, ty: type) -> Any:
    if ty is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if ty is dict or ty is list:
        return json.loads(value)
    return ty(value)


@dataclass
class Config:
    # --- scheduling ---
    scheduler_spread_threshold: float = 0.5   # hybrid policy: pack below, spread above
    lease_spill_min_queue_s: float = 0.5      # queued-lease settle time before spillback
    scheduler_top_k_fraction: float = 0.2     # top-k random choice among best nodes
    max_pending_lease_requests_per_scheduling_class: int = 10
    worker_lease_timeout_ms: int = 500
    # --- object store ---
    object_store_memory_bytes: int = 2 * 1024**3
    object_store_small_object_threshold: int = 100 * 1024  # inline below this
    object_spilling_enabled: bool = True      # evictees spill to disk
    object_spilling_dir: str = ""             # "" = TEMP_ROOT/spill/<store>
    object_spilling_threshold: float = 0.8
    object_store_eviction_fraction: float = 0.1
    # spill/restore I/O plane (object_store.py): chunked multi-worker
    # copies straight between spill files and the shm mapping (preadv/
    # sendfile, no intermediate bytes). Workers size the shared I/O
    # pool; restores additionally admit through a bytes-in-flight gate
    # that shares object_transfer_max_inflight_bytes with PullManager
    # so concurrent restores can't blow the store.
    object_spill_io_workers: int = 4
    object_spill_io_chunk_bytes: int = 8 * 1024**2
    # --- data shuffle (data/shuffle.py map/merge exchange) ---
    # partitions per exchange; 0 = auto (sort: max(input blocks,
    # total/fragment_target); random_shuffle: total/fragment_target,
    # layout-independent so a fixed seed is reproducible across block
    # layouts; groupby: fixed small default so maps pipeline)
    shuffle_num_partitions: int = 0
    # auto-partitioning aims each merged output block at this size
    shuffle_fragment_target_bytes: int = 16 * 1024**2
    # merge-task submission window (per-partition merges in flight)
    shuffle_merge_parallelism: int = 8
    # --- memory pressure (ref: memory_monitor.h:52 + killing policies) ---
    memory_monitor_refresh_ms: int = 500      # 0 disables the monitor
    memory_usage_threshold: float = 0.95      # host RSS fraction to act at
    memory_monitor_test_file: str = ""        # tests: file with a fraction
    max_grpc_message_bytes: int = 512 * 1024**2
    object_transfer_chunk_bytes: int = 8 * 1024**2
    # bulk transfer plane (object_transfer.py): parallel raw-frame
    # connections per pull, and the PullManager's bytes-in-flight budget
    object_transfer_streams: int = 4
    object_transfer_max_inflight_bytes: int = 512 * 1024**2
    # broadcast tree: a holder grants at most this many concurrent
    # senders-per-object; denied pullers re-poll the directory and
    # chain off freshly-completed copies instead of piling onto the one
    # origin (ref: push_manager.h:32 per-peer in-flight caps; BASELINE
    # envelope row: 1 GiB broadcast to 50+ nodes). Cost: one extra small
    # acquire RPC per cross-node pull (release is fire-and-forget);
    # latency-critical small-object workloads can set 0 to disable
    # gating entirely (no RPC is made then).
    object_transfer_max_senders_per_object: int = 2
    # --- fast lane (native shm task plane; ray_tpu/_private/fastlane.py) ---
    fastlane_width: int = 4                   # max lanes (leased workers)
    fastlane_window: int = 32                 # in-flight tasks per lane
    # max actors with an open fast lane per owner (each lane = 2 shm
    # rings + 2 threads); calls beyond the cap ride the asyncio path
    actor_lane_max: int = 64
    # --- workers ---
    num_workers_soft_limit: int = -1          # -1: num_cpus
    worker_startup_timeout_s: float = 60.0
    # forkserver worker factory (worker_factory.py): pay worker imports
    # once per node, fork per worker. Off = cold Popen per worker.
    worker_factory_enabled: bool = True
    # max workers mid-startup at once (fork-storm guard for envelope-
    # depth actor counts; dedicated spawns queue behind the burst)
    worker_spawn_burst: int = 16
    # dialing an already-registered worker (its RPC server is live): short
    worker_dial_timeout_s: float = 3.0
    worker_register_timeout_s: float = 30.0
    idle_worker_killing_time_threshold_ms: int = 800
    prestart_workers: bool = True
    # --- fault tolerance ---
    task_max_retries_default: int = 3
    actor_max_restarts_default: int = 0
    health_check_period_ms: int = 1000
    health_check_failure_threshold: int = 5
    # per-probe RPC timeout for the GCS's ACTIVE node health checks
    # (ref: gcs_health_check_manager.h kDefaultTimeoutMs); 0 disables
    # active probing (disconnect-only death detection)
    health_check_timeout_ms: int = 2000
    # resource view propagation (syncer.py): "hub" = GCS pubsub fan-out
    # (O(N^2) msgs/interval through one loop), "gossip" = push-pull
    # anti-entropy, O(fanout) per node, O(log N) rounds to converge
    # (ref: ray_syncer.h:83)
    resource_sync_mode: str = "hub"
    resource_sync_interval_s: float = 1.0
    resource_sync_fanout: int = 2
    lineage_pinning_enabled: bool = True
    max_lineage_bytes: int = 1024**3
    # --- chaos / testing (mirrors rpc_chaos.h fault injection) ---
    testing_rpc_failure: str = ""             # "method=prob_req:prob_resp,..."
    # failpoint harness (_private/failpoints.py): named fault-injection
    # sites at the hazard boundaries the graftlint error-plane passes
    # audit. "site=action[:arg][:max_hits],..." — actions raise/delay/
    # drop; "site@detail=..." scopes to one RPC method. Empty = every
    # site is a single dict lookup (inert).
    failpoints: str = ""
    # graftlint runtime lock-order witness (devtools/graftlint/witness):
    # control-plane locks built through _private/locking.py become
    # instrumented WitnessLocks feeding a global lockdep-style order
    # graph that raises on cycle formation. Debug/CI-stress only —
    # read at lock CONSTRUCTION, so flip it before init().
    lock_witness_enabled: bool = False
    # locality-aware leasing: lease at the node holding a task's argument
    # bytes when the known dependency mass there reaches this many bytes
    # (ref: lease_policy.h LocalityAwareLeasePolicy). 0 disables.
    scheduler_locality_min_bytes: int = 64 * 1024
    # per-try timeout for lease RPCs; 0 = wait forever (reliable transport).
    # Chaos/unreliable setups set this so dropped frames trigger a retry,
    # which the raylet dedups by request id.
    lease_rpc_timeout_s: float = 0.0
    # bound on the GCS's outbound control RPCs to raylets (placement-
    # group reserve/commit/cancel fan-out): a dead or wedged raylet
    # surfaces as GcsTimeoutError instead of hanging the scheduling
    # loop. 0 = wait forever.
    gcs_rpc_timeout_s: float = 30.0
    # --- stall sentinel (hang/straggler detection) ---
    # raylet task watchdog period; 0 disables the watchdog. Each tick the
    # raylet compares every RUNNING task's age against an adaptive
    # per-scheduling-class threshold (EMA of completed durations times
    # task_stall_ema_factor, floored at task_stall_threshold_s), captures
    # the implicated worker's stack via its dump_stacks RPC, and emits a
    # WARNING cluster event with the stack attached.
    task_watchdog_interval_s: float = 5.0
    # floor for the adaptive RUNNING-too-long threshold; a class with no
    # completion history yet stalls only past this floor
    task_stall_threshold_s: float = 60.0
    # a task is suspect once it runs this multiple of its class's EMA
    task_stall_ema_factor: float = 10.0
    # GCS collective watchdog period; 0 disables. A collective step with
    # some-but-not-all participant arrivals older than
    # collective_stall_timeout_s emits a "hung collective" event naming
    # the missing ranks/hosts and pulls their stacks.
    collective_watchdog_interval_s: float = 2.0
    collective_stall_timeout_s: float = 30.0
    # transfer stall detector: a pull whose contiguous byte watermark has
    # not advanced for this long is flagged (0 disables); checked by the
    # raylet watchdog tick against the store's in-progress registry.
    transfer_stall_timeout_s: float = 30.0
    # --- tail tolerance (hedged execution + straggler-aware scheduling,
    #     ref: The Tail at Scale — the mitigation half of the stall
    #     sentinel's detection plane) ---
    # speculative re-execution of idempotent tasks: when a RUNNING task
    # outlives its hedge delay (per-fn EMA of past push->reply durations
    # times task_hedge_ema_factor, floored at task_hedge_min_delay_s) or
    # the raylet watchdog flags it and hints the owner, the owner pushes
    # a second copy of the same TaskSpec to a different node; the first
    # reply wins and is published exactly once, the loser is cancelled.
    # Only tasks declared @remote(idempotent=True) (or
    # speculation="auto") are eligible.
    task_speculation_enabled: bool = False
    task_hedge_ema_factor: float = 3.0
    task_hedge_min_delay_s: float = 1.0
    # serve request hedging (serve/handle.py): once a handle has
    # serve_hedge_min_samples latency samples, a request still pending
    # past that sample set's serve_hedge_quantile latency is hedged to
    # the second-choice replica (first response wins, loser's reply is
    # discarded), provided hedges stay under serve_hedge_budget of total
    # requests. 0.0 disables hedging entirely (default: zero overhead).
    serve_hedge_quantile: float = 0.0
    serve_hedge_budget: float = 0.05
    serve_hedge_min_samples: int = 16
    # --- fleet KV plane (serve/kv_router.py): prefix-cache-aware
    #     routing + disaggregated prefill/decode serving ---
    # route requests to the replica holding the longest cached prompt
    # prefix (replicas publish truncated prefix-page digests through the
    # controller's reconcile tick); off = pure pow-2 load routing
    serve_prefix_routing_enabled: bool = True
    # how often the controller re-polls replica prefix summaries AND how
    # often handles re-pull the aggregated table; a summary older than
    # 3x this is stale and the handle falls back to load routing
    serve_prefix_summary_interval_s: float = 2.0
    # spill threshold: a prefix-match winner with more than this many
    # of the handle's own in-flight requests loses to pow-2 (cache
    # affinity must not defeat load balancing under a hot prefix)
    serve_prefix_spill_queue_depth: int = 8
    # prefill->decode KV handoff: exported page payloads are split into
    # object-store puts of at most this many bytes so one long prompt's
    # KV doesn't serialize as a single giant object
    serve_kv_handoff_chunk_bytes: int = 8 * 1024**2
    # speculative decoding, fleet verify mode: decode-pool replicas
    # corroborate their local draft verification against the prefill
    # pool (which batch-verifies on otherwise-idle decode-phase chips).
    # Off by default — the local verify is always authoritative; fleet
    # verify adds cross-pool agreement counters and warms the path for
    # drafter-on-decode / verifier-on-prefill placements.
    llm_spec_fleet_verify: bool = False
    llm_spec_fleet_verify_timeout_s: float = 2.0
    # straggler-aware scheduling: the raylet refreshes per-node straggler
    # scores (GCS lateness EMA relative to cluster mean) on its watchdog
    # tick and deprioritizes nodes scoring >= this threshold in spread /
    # hybrid placement whenever a non-straggler alternative is feasible.
    # 0 disables score-based deprioritization (avoid_nodes still works).
    straggler_deprioritize_threshold: float = 3.0
    # drain-and-restart: when the watchdog flags a non-actor task wedged
    # past straggler_drain_after_factor x its stall threshold, the raylet
    # kills the worker so the owner's retry path resubmits elsewhere —
    # rescuing gang collectives before CollectiveTimeoutError. Off by
    # default: it trades a duplicate execution for tail latency.
    straggler_drain_enabled: bool = False
    straggler_drain_after_factor: float = 2.0
    # --- profiling & memory attribution plane (util/stacks.py,
    #     util/hbm.py, state.memory_report; ref: Google-Wide Profiling —
    #     always-on sampling at <1% overhead) ---
    # always-on per-worker sampling profiler rate (folded wall/CPU
    # stacks, drained by `cli profile` / the GCS merge). 0 disables the
    # ambient sampler entirely; on-demand bursts still work at any rate.
    profiling_sample_hz: float = 0.0
    # frames kept per sampled stack (deeper frames are truncated)
    profiling_max_stack_depth: int = 64
    # submit-path stage timers (core_worker.submit_task histograms, the
    # ROADMAP item-2 baseline instrument). Off = zero perf_counter reads
    # on the submit hot path.
    submit_stage_timers_enabled: bool = True
    # start tracemalloc in every worker so memory_report can attribute
    # per-worker Python heap deltas (tracemalloc costs ~2x allocation
    # overhead — opt-in)
    tracemalloc_enabled: bool = False
    # HBM gauge publication period (per-chip live-buffer/fragmentation
    # gauges read from the jax backend, piggybacked on the stall-probe
    # tick). 0 disables.
    hbm_gauge_interval_s: float = 10.0
    # memory_report flags a pinned, ownerless object older than this as
    # a leak suspect
    memory_leak_age_s: float = 60.0
    # --- logging / metrics ---
    event_log_enabled: bool = True
    metrics_report_interval_ms: int = 2000
    # --- SLO observability plane (ray_tpu/slo.py; GCS-side series
    #     retention + burn-rate monitor) ---
    # keep per-series ring buffers of the aggregated metrics table,
    # sampled on the GCS evaluation tick (the in-memory-TSDB layer the
    # SLO monitor and dashboard sparklines read). Off = last-value-only
    # metrics table, SLO engine inert.
    metrics_series_enabled: bool = True
    # ring length per series; retention ~= max_samples x min_interval
    metrics_series_max_samples: int = 256
    # downsampling floor: appends closer together than this are dropped
    metrics_series_min_interval_s: float = 2.0
    # total series bound, FIFO-evicted (tenant tags multiply cardinality)
    metrics_series_max_series: int = 4000
    # GCS sampling + SLO evaluation tick; 0 disables the loop entirely
    slo_eval_interval_s: float = 2.0
    # declarative SLO specs, each "name: indicator op value [@ k=v,...]
    # [window=60s]" — e.g. "chat-ttft: ttft_p99 < 250ms @ tenant=acme",
    # "chat-avail: availability >= 99.9% @ deployment=Chat". Also
    # settable at runtime via state.set_slo_specs / the loadgen.
    slo_specs: list = field(default_factory=list)
    # multi-window burn-rate alerting (SRE Workbook ch.5): an alert
    # fires when the error-budget burn rate exceeds the threshold over
    # BOTH windows of a pair ("short,long" seconds). Fast pair emits
    # ERROR events, slow pair WARNING. Defaults are the Workbook's
    # 5m/1h + 30m/6h shape scaled to this cluster's 2 s ticks.
    slo_fast_burn_windows_s: str = "30,300"
    slo_fast_burn_threshold: float = 14.4
    slo_slow_burn_windows_s: str = "120,600"
    slo_slow_burn_threshold: float = 6.0
    # tenant id assumed for requests arriving without an X-Tenant-ID
    # header (per-tenant accounting; serve/proxy.py)
    serve_default_tenant: str = "default"
    # --- black-box plane (_private/blackbox.py: flight rings, crash
    #     bundles, durable observability state; read by cli postmortem) ---
    # per-process flight recorder: bounded ring of recent events/logs/
    # stacks/in-flight ids, flushed to a session-dir flight file and
    # promoted to a crash bundle on abnormal exit or survivor sweep.
    blackbox_enabled: bool = True
    # ring length (events and log records each keep this many entries)
    blackbox_ring_size: int = 256
    # flight-file rewrite period; bounds how stale a SIGKILL'd corpse's
    # bundle can be. Appends are off the submit hot path either way.
    blackbox_flush_interval_s: float = 2.0
    # GCS durable-observability checkpoint period (SeriesStore rings,
    # SLO monitor state, aggregated metrics table, task-event table →
    # gcs_storage). 0 disables checkpointing; restore still runs if a
    # prior checkpoint exists in the journal.
    obs_checkpoint_interval_s: float = 10.0
    # persist cluster events as JSONL next to the bundles so
    # `cli events --follow` works against a dead cluster
    event_journal_enabled: bool = True
    # after restoring SLO state on head restart, suppress NEW burn-rate
    # alert transitions for this long — the restart gap must not page
    slo_restore_grace_s: float = 30.0
    # raylet clock-sync period against the GCS clock (NTP-style offset
    # piggybacked on ping; raylet.py _clock_sync_loop). 0 disables —
    # timelines then merge raw per-node wall clocks.
    clock_sync_interval_s: float = 30.0
    # --- training goodput plane (train/telemetry.py; GCS-side ledger
    #     in _private/gcs.py handle_train_report) ---
    # per-step phase telemetry: timeline in train/session.py, compile/
    # compute attribution in train/step.py. Off = bare jitted step
    # (no per-call device sync), no TrainStepTelemetry records.
    train_telemetry_enabled: bool = True
    # first-call-per-shape faster than this with no new persistent-cache
    # entries classifies as a cache hit rather than a cold compile
    train_compile_cache_hit_threshold_s: float = 0.5
    # accelerator peak (bf16 matmul) flops per chip for MFU math —
    # 0 leaves MFU unreported (v5p ~459e12, v5e ~197e12)
    train_peak_flops_per_chip: float = 0.0
    # --- device plane ---
    # bind host for the per-process PJRT transfer server backing
    # DeviceChannel (experimental/device_channel.py); must be routable
    # from peer hosts — "" = loopback (single host). TPU pods set the
    # node's DCN-reachable IP.
    device_transfer_host: str = ""
    default_device_platform: str = ""         # "" = jax default
    ici_mesh_auto_axis_order: bool = True

    def apply_overrides(self, overrides: Dict[str, Any]) -> None:
        valid = {f.name: f.type for f in fields(self)}
        for key, value in overrides.items():
            if key not in valid:
                raise ValueError(f"Unknown config flag: {key}")
            setattr(self, key, value)

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls()
        for f in fields(cls):
            env_key = _ENV_PREFIX + f.name.upper()
            if env_key in os.environ:
                ty = type(getattr(cfg, f.name))
                setattr(cfg, f.name, _coerce(os.environ[env_key], ty))
        return cfg

    def to_dict(self) -> Dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)}


_global_config: Config | None = None


def global_config() -> Config:
    global _global_config
    if _global_config is None:
        _global_config = Config.from_env()
    return _global_config


def reset_global_config() -> None:
    global _global_config
    _global_config = None
