"""LLM serving deployment: the engine behind an async serve replica.

Reference analog: ray.llm's serve deployments
(llm/_internal/serve/deployments/llm/llm_server.py wrapping vLLM's async
engine, + the OpenAI router in _internal/serve/deployments/routers/).
Here the continuous-batching engine runs on a replica-side thread; each
request registers an asyncio queue that the engine pump feeds, so many
HTTP streams multiplex over ONE decode batch — the continuous-batching
payoff serve exists to deliver.

Usage:
    app = build_llm_deployment("tiny", init="random")   # or params blob
    handle = serve.run(app)
    out = await handle.completions.remote({"prompt_ids": [...]})
"""

from __future__ import annotations

import asyncio
import json
import os
import time
from typing import TYPE_CHECKING, Any, Dict, List, Optional

from ..util import tracing

if TYPE_CHECKING:  # this module stays importable without jax (llm/__init__)
    from .sampling import SamplingParams

# The longest piece of ``rt.pump.lull``. A wait for a request can last
# minutes, and an annotation that was open when a profiler started is
# lost to it; the benchmark's trace reduction also drops a host event
# over 20 times as long as the device gap it is held against.
LULL_PIECE_S = 0.05


class LLMServer:
    """Serve deployment class hosting one engine replica."""

    def __init__(self, model="tiny", *, init: str = "random",
                 params_path: Optional[str] = None,
                 engine_config: Optional[dict] = None,
                 tokenizer: Optional[str] = None, seed: int = 0,
                 quantize: Optional[str] = None,
                 speculation: Optional[dict] = None,
                 seed_gains: Optional[dict] = None):
        import jax

        from .. import get_tpu_chip_ids
        from .._private import device_plane
        from ..models.llama import LLAMA_CONFIGS, LlamaConfig, init_params
        from .engine import EngineConfig, LLMEngine

        chip_ids = get_tpu_chip_ids()
        self._device = jax.devices()[0]
        if chip_ids and self._device.platform != "tpu":
            raise RuntimeError(
                f"LLMServer replica holds TPU chips {chip_ids} but jax "
                f"started on {self._device.platform!r}: refusing to "
                f"serve a chip lease from another backend")
        self._compile_cache_dir = device_plane.enable_compilation_cache()
        if isinstance(model, LlamaConfig):
            # a configuration itself (random or pickled weights): no
            # entry in LLAMA_CONFIGS is needed, and none is written
            cfg, model = model, (
                f"llama-{model.n_layers}x{model.dim}"
                + (f"-{model.n_experts}e" if model.n_experts else ""))
        elif model in LLAMA_CONFIGS:
            cfg = LLAMA_CONFIGS[model]
        elif os.path.isdir(model):
            cfg = None  # an HF checkpoint directory IS the model source
        else:
            raise ValueError(f"unknown model {model!r}: not a named "
                             f"config or an HF checkpoint dir")
        self.model_name = model
        if cfg is None or init == "hf":
            # real weights: HF safetensors directory (hf_interop.py) —
            # the vLLM-engine weight-loading analog
            from ..models.hf_interop import load_hf_checkpoint

            path = model if cfg is None else (params_path or model)
            if not os.path.isdir(path):
                raise ValueError(
                    f"init='hf' needs an HF checkpoint directory; "
                    f"{path!r} is not one (pass it as `model` or "
                    f"`params_path`)")
            # quantize="int8": host-side per-channel int8 before the
            # device sees anything — how Llama-3-8B serves on one 16 GB
            # chip (ops/quant.py)
            params, cfg = load_hf_checkpoint(path, quantize=quantize)
            params = jax.device_put(params)
            if tokenizer is None and os.path.exists(
                    os.path.join(path, "tokenizer_config.json")):
                tokenizer = path
        elif params_path:
            import pickle

            if quantize is not None:
                raise ValueError(
                    "quantize applies to HF-checkpoint loading only "
                    "(init='hf' / a checkpoint-dir model)")
            with open(params_path, "rb") as f:
                params = pickle.load(f)
            params = jax.device_put(params)
        elif init == "random":
            # seed_gains: a matrix's name -> a factor on its seeded scale
            # (models.llama.init_params)
            if quantize is None:
                params = init_params(jax.random.PRNGKey(seed), cfg,
                                     seed_gains)
            elif quantize == "int8":
                # seeded int8 weights made on the device: the only way a
                # machine without a checkpoint holds Llama-3-8B on one
                # 16 GB chip (ops/quant.py)
                from ..ops.quant import init_params_quantized

                params = init_params_quantized(jax.random.PRNGKey(seed),
                                               cfg, seed_gains)
            else:
                raise ValueError(f"unknown quantize {quantize!r}")
        else:
            raise ValueError(f"unknown init {init!r}")
        ecfg = EngineConfig(**(engine_config or {}))
        self.engine = LLMEngine(params, cfg, ecfg)
        self.tokenizer = None
        if tokenizer:
            from transformers import AutoTokenizer

            self.tokenizer = AutoTokenizer.from_pretrained(tokenizer)
        self._queues: Dict[str, asyncio.Queue] = {}
        self._pump_task: Optional[asyncio.Task] = None
        # the open piece of a lull: an arrival (_ensure_pump) or the
        # piece's own timer resolves it
        self._lull_wake: Optional[asyncio.Future] = None
        # seconds from the pump's own spans (stats()["pump"])
        self._pump_stats = {"lull_s": 0.0, "lulls": 0}
        # per-tenant accounting: request id -> tenant, stashed at submit
        # (the serve tenant contextvar is gone by the time the pump
        # thread observes the finished request) and popped on finish
        self._tenants: Dict[str, str] = {}
        # fleet KV plane (disaggregated serving): pool role, set by the
        # replica's configure_pool hook before any request lands.
        # "mono" = classic all-in-one replica; "prefill" runs prompt
        # passes only and ships KV to the decode pool; "decode" accepts
        # injected KV and runs decode only.
        self._pool = "mono"
        self._dep_name: Optional[str] = None
        self._decode_handle = None
        self._m_handoff_bytes = None
        self._m_handoff_lat = None
        self._m_handoff_retries = None
        self._last_summary = None
        # serializes engine mutation between the pump's executor thread
        # and loop-side KV export/inject
        import threading

        self._engine_lock = threading.Lock()
        # serving metrics (ref: vLLM's engine stat logger — TTFT/TPOT
        # histograms, scheduler-state and cache-hit gauges), exported
        # through the util.metrics -> GCS -> /metrics pipeline. The
        # "pool" tag splits TTFT/TPOT by replica role so disaggregated
        # deployments meter prefill and decode separately.
        from ..util import metrics

        tags = {"model": self.model_name, "pool": self._pool}
        self._m_ttft = metrics.Histogram(
            "llm_ttft_seconds", "Time to first token per request",
            boundaries=metrics.LATENCY_BUCKETS,
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_tpot = metrics.Histogram(
            "llm_tpot_seconds", "Time per output token (decode) "
            "per request", boundaries=metrics.LATENCY_BUCKETS,
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_queue_wait = metrics.Histogram(
            "llm_queue_wait_seconds", "Arrival to first slot and pages: "
            "the part of TTFT spent waiting to be admitted",
            boundaries=metrics.LATENCY_BUCKETS,
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_preemptions = metrics.Counter(
            "llm_preemptions_total",
            "Recompute-preemptions of running requests (KV pool ran dry)",
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_e2e = metrics.Histogram(
            "llm_request_e2e_seconds", "Arrival-to-finish request latency",
            boundaries=metrics.LATENCY_BUCKETS,
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_queue = metrics.Gauge(
            "llm_queue_depth", "Requests waiting for a decode slot",
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._m_occupancy = metrics.Gauge(
            "llm_batch_slot_occupancy",
            "Fraction of decode slots running (continuous batching)",
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._m_kv_util = metrics.Gauge(
            "llm_kv_page_utilization", "Fraction of KV-cache pages in use",
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._m_kv_free = metrics.Gauge(
            "llm_kv_free_pages", "Free KV-cache pages of a layer group "
            "(full: layers that see the whole sequence; window: layers "
            "that give back what has left their window)",
            tag_keys=("model", "pool", "group")).set_default_tags(tags)
        self._m_cache_hit = metrics.Counter(
            "llm_prefix_cache_hit_tokens_total",
            "Prompt tokens served from the prefix cache",
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_prompt = metrics.Counter(
            "llm_prompt_tokens_total", "Prompt tokens received",
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        self._m_generated = metrics.Counter(
            "llm_generation_tokens_total", "Tokens generated",
            tag_keys=("model", "pool", "tenant")).set_default_tags(tags)
        # speculative decoding (llm/spec_decode.py): per-round counters
        # drained from the engine's SpecDecoder by the pump. The
        # acceptance ratio is THE health signal — a drafter that stops
        # agreeing with the target turns every verify into one-token
        # decode plus wasted draft FLOPs.
        self._m_spec_drafted = metrics.Counter(
            "llm_spec_draft_tokens_total",
            "Draft tokens proposed by the speculation drafter",
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._m_spec_accepted = metrics.Counter(
            "llm_spec_accepted_tokens_total",
            "Draft tokens accepted by target verification",
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._m_spec_ratio = metrics.Gauge(
            "llm_spec_acceptance_ratio",
            "Cumulative accepted/drafted token ratio",
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._m_spec_verify = metrics.Histogram(
            "llm_spec_verify_seconds",
            "Target-model batched verify forward latency",
            boundaries=metrics.LATENCY_BUCKETS,
            tag_keys=("model", "pool")).set_default_tags(tags)
        self._spec_seen = {"drafted": 0, "accepted": 0}
        self._verify_handle = None
        if speculation:
            self.configure_speculation(speculation)
        self._mark_lulls()

    # --- serve replica hooks (fleet KV plane) ---

    def configure_pool(self, pool: Optional[str],
                       deployment_name: str) -> None:
        """Replica hook: learn this replica's role in a disaggregated
        deployment. Prefill replicas skip decode in their pump and ship
        finished prompt KV to the decode pool; metrics re-tag so
        TTFT/TPOT split by pool. Called in the replica's constructor, so
        before it reports ready: a replica that decodes loads every shape
        a decode round can take here (lone warm-up requests reach only
        the smallest page lists); a prefill replica never runs one. A
        replica that prefills loads the whole-prompt programs no ladder of
        lone requests meets (``LLMEngine.load_prefill_programs``); a
        decode replica is handed its prompts' KV and never runs one."""
        if pool in ("prefill", "decode") and len(self.engine.windows) > 1:
            raise ValueError(
                f"pool={pool!r} (prefill and decode replicas that hand KV "
                f"pages over) is not supported with "
                f"{len(self.engine.windows)} layer groups: a KV payload is "
                f"one stack of pages for all layers")
        self._pool = pool or "mono"
        self._dep_name = deployment_name
        if pool != "prefill":
            self.engine.load_decode_programs()
        if pool != "decode":
            self.engine.load_prefill_programs()
        tags = {"model": self.model_name, "pool": self._pool}
        for m in (self._m_ttft, self._m_tpot, self._m_queue_wait,
                  self._m_preemptions, self._m_e2e, self._m_queue,
                  self._m_occupancy, self._m_kv_util, self._m_kv_free,
                  self._m_cache_hit,
                  self._m_prompt, self._m_generated, self._m_spec_drafted,
                  self._m_spec_accepted, self._m_spec_ratio,
                  self._m_spec_verify):
            m.set_default_tags(tags)
        if pool == "decode":
            self._configure_fleet_verify(deployment_name)
        if pool == "prefill":
            from ..serve.handle import DeploymentHandle
            from ..util import metrics

            self._decode_handle = DeploymentHandle(
                deployment_name, "decode_from_kv", pool="decode")
            mtags = {"model": self.model_name}
            self._m_handoff_bytes = metrics.Counter(
                "serve_kv_handoff_bytes_total",
                "KV page bytes shipped prefill->decode",
                tag_keys=("model",)).set_default_tags(mtags)
            self._m_handoff_lat = metrics.Histogram(
                "serve_kv_handoff_seconds",
                "Prefill->decode KV handoff latency (export+ship+reply)",
                boundaries=metrics.LATENCY_BUCKETS,
                tag_keys=("model",)).set_default_tags(mtags)
            self._m_handoff_retries = metrics.Counter(
                "serve_kv_handoff_retries_total",
                "KV handoffs retried against another decode replica",
                tag_keys=("model",)).set_default_tags(mtags)
        self._mark_lulls()

    def _mark_lulls(self) -> None:
        """A replica without a request is in a lull from here on. The
        pump is a task of the event loop; where this thread runs none
        (``serve``'s ``Replica`` makes its callable before the actor's
        loop exists) the first call that reaches the loop starts it: the
        controller's ``check_health`` or a request."""
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return
        self._ensure_pump()

    async def check_health(self) -> None:
        """Replica hook (``Replica.health_check``, the controller's
        probe). It runs on the replica's loop, so a replica that has not
        had a request marks its lulls from its first probe."""
        self._ensure_pump()

    def prefix_cache_summary(self):
        """Replica hook: publish this engine's cached prefix pages for
        the fleet KV router (serve/kv_router.py). None when prefix
        caching is off — the controller then stops polling this
        deployment version entirely.

        Never blocks on the engine lock: a step can hold it for seconds
        (jit compile), and waiting here would stall the replica's whole
        event loop and time out the controller's gossip probe. When the
        engine is mid-step, the previous snapshot goes out instead —
        routing hints tolerate a tick of staleness by design."""
        cache = self.engine.prefix_cache
        if cache is None:
            return None
        from ..serve import kv_router

        if self._engine_lock.acquire(blocking=False):
            try:
                keys = list(cache._pages.keys())
            finally:
                self._engine_lock.release()
            self._last_summary = kv_router.make_summary(
                keys, self.engine.ecfg.page_size)
        if self._last_summary is None:
            # first poll raced a step: publish an empty summary, NOT
            # None — None means "no hook" and stops gossip for good
            return kv_router.make_summary(
                (), self.engine.ecfg.page_size)
        return self._last_summary

    # --- speculative decoding (llm/spec_decode.py) ---

    def configure_speculation(self, spec) -> None:
        """Enable draft/verify speculative decoding on this replica's
        engine. Reached two ways: the LLMServer ``speculation`` kwarg
        (build_llm_deployment) and the serve deployment-config override
        (the Replica hook), so YAML deploys can toggle it without
        re-pickling init args."""
        if not spec:
            return
        with self._engine_lock:
            self.engine.enable_speculation(spec)

    def _configure_fleet_verify(self, deployment_name: str) -> None:
        """Decode-pool replica in fleet-verify mode: drafting happens
        here (decode chips idle between target forwards); the prefill
        pool batch-verifies each drafted window against a KV snapshot
        shipped through the object store. The local verify stays
        authoritative — the remote result corroborates it (agreement
        counters on the engine's SpecDecoder), so a lagging or dead
        prefill pool can never wrong or wedge a decode round."""
        from .._private.config import global_config

        if self.engine.spec is None \
                or not global_config().llm_spec_fleet_verify:
            return
        from ..serve.handle import DeploymentHandle

        self._verify_handle = DeploymentHandle(
            deployment_name, "verify_draft", pool="prefill")

        def _fleet_verify(payload, draft):
            # runs on the pump's executor thread inside the engine's
            # spec round: bounded by the fleet-verify timeout so a slow
            # prefill pool degrades to local-only, never a stall
            from .. import get, put

            k = payload.pop("k")
            v = payload.pop("v")
            ref = put((k, v))
            out_ref, _replica = self._verify_handle.route(
                {"handoff": payload, "kv_ref": ref,
                 "draft": [int(t) for t in draft]})
            out = get(out_ref,
                      timeout=global_config().llm_spec_fleet_verify_timeout_s)
            return None if out is None else [int(t) for t in out]

        self.engine._spec_remote_verify = _fleet_verify

    async def verify_draft(self, payload: Dict[str, Any]):
        """Prefill-pool (or any) replica endpoint: verify one drafted
        window against this replica's target weights. The KV snapshot
        rides the object store; an unusable snapshot falls back to
        recomputing the prefix inside remote_verify — slower, never
        wrong. Returns the emitted tokens (accepted prefix + the
        target's correction/bonus token)."""
        from .. import get
        from .spec_decode import remote_verify

        loop = asyncio.get_event_loop()
        meta = dict(payload["handoff"])
        if payload.get("kv_ref") is not None:
            k, v = await loop.run_in_executor(
                None, lambda: get(payload["kv_ref"], timeout=30))
            meta["k"] = k
            meta["v"] = v
        draft = [int(t) for t in payload["draft"]]

        def _run():
            with self._engine_lock:
                return remote_verify(self.engine, meta, draft)

        return await loop.run_in_executor(None, _run)

    def _drain_spec_stats(self) -> None:
        """Fold the engine SpecDecoder's cumulative counters into the
        serve metrics as deltas (the pump calls this every step)."""
        spec = self.engine.spec
        if spec is None:
            return
        d = spec.drafted_total - self._spec_seen["drafted"]
        a = spec.accepted_total - self._spec_seen["accepted"]
        if d:
            self._m_spec_drafted.inc(d)
            self._spec_seen["drafted"] = spec.drafted_total
        if a:
            self._m_spec_accepted.inc(a)
            self._spec_seen["accepted"] = spec.accepted_total
        if spec.drafted_total:
            self._m_spec_ratio.set(spec.acceptance_ratio)
        for t in spec.take_verify_times():
            self._m_spec_verify.observe(t)

    # --- engine pump: one thread-hop per step, fan-out to request queues ---

    def _ensure_pump(self) -> None:
        """Called on the loop's thread once a request is in the engine's
        hands: start the pump where none runs, and end its lull at once
        (the pump resumes on the loop's next turn, as a new task would
        start)."""
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.get_event_loop().create_task(
                self._pump())
        self._end_lull_piece()

    def _end_lull_piece(self) -> None:
        wake = self._lull_wake
        if wake is not None and not wake.done():
            wake.set_result(None)

    def _step_engine(self):
        # prefill replicas never decode: exported requests finish with
        # the handoff, so decode slots would only ever idle-spin
        with self._engine_lock:
            return self.engine.step(
                skip_decode=(self._pool == "prefill"))

    async def _pump(self) -> None:
        """The replica's one long-lived task: rounds while the engine
        has a request, a lull while it has none."""
        while True:
            await self._run_rounds()
            await self._lull()

    async def _lull(self) -> None:
        """``rt.pump.lull``: no request is in the engine, and the device
        waits for want of traffic. ``rt.pump.idle`` is the other wait:
        requests ARE unfinished, a round returned nothing, and the pump
        sleeps 2 ms before the next. The lull is cut into pieces of at
        most ``LULL_PIECE_S`` and waits on a future, not on a poll: an
        arrival resolves it. The pieces are annotations; the JSONL sink
        gets one record for the whole lull."""
        loop = asyncio.get_event_loop()
        stats = self._pump_stats
        wall0 = time.time() if tracing.tracing_enabled() else 0.0
        waited_before = stats["lull_s"]
        stats["lulls"] += 1
        try:
            while not self.engine.has_unfinished():
                self._lull_wake = loop.create_future()
                timer = loop.call_later(LULL_PIECE_S, self._end_lull_piece)
                piece = tracing.piece_span("rt.pump.lull")
                try:
                    with piece:
                        await self._lull_wake
                finally:
                    timer.cancel()
                    stats["lull_s"] += piece.seconds
        finally:
            self._lull_wake = None
            if wall0:
                tracing.record_lane_event(
                    "pump", "rt.pump.lull", wall0,
                    wall0 + stats["lull_s"] - waited_before)

    async def _run_rounds(self) -> None:
        loop = asyncio.get_event_loop()
        requests = self.engine.requests
        while self.engine.has_unfinished():
            outs = await loop.run_in_executor(None, self._step_engine)
            # everything between one round's return and the next
            # submission: the device waits for it
            with tracing.span("rt.pump.fanout"):
                for out in outs:
                    q = self._queues.get(out.request_id)
                    if q is not None:
                        q.put_nowait(out)
                    if out.text_offset == 0:
                        # the request's first token leaves the engine's
                        # hands here, a decode round after it was sampled
                        first = requests.get(out.request_id)
                        if first is not None:
                            first.emit_t = time.perf_counter()
                    if out.finished:
                        # the reader holds its queue reference; drop ours
                        # and the engine's state so a long-lived replica
                        # doesn't accumulate every past request
                        self._queues.pop(out.request_id, None)
                        state = requests.pop(out.request_id, None)
                        if state is not None:
                            self._observe_finished(state,
                                                   time.perf_counter())
                stats = self.engine.stats()
                self._drain_spec_stats()
                self._m_queue.set(stats["waiting"])
                self._m_occupancy.set(
                    stats["running"]
                    / max(1, self.engine.ecfg.max_num_seqs))
                self._m_kv_util.set(
                    1.0 - stats["free_pages"]
                    / max(1, stats["total_pages"]))
                for name, group in stats["counters"]["groups"].items():
                    self._m_kv_free.set(group["free_pages"],
                                        tags={"group": name})
            if not outs:
                with tracing.span("rt.pump.idle"):
                    await asyncio.sleep(0.002)

    def _observe_finished(self, state, now: float) -> None:
        """Fold one finished request into the latency histograms.
        Timestamps are engine-side perf_counter marks (RequestState
        arrival_t / first_token_t), so TTFT includes queueing."""
        tags = {}
        if state.model_id:
            tags["model"] = state.model_id
        tenant = self._tenants.pop(state.request_id, None)
        if tenant:
            tags["tenant"] = tenant
        tags = tags or None
        n_out = len(state.output)
        if state.admit_t:
            self._m_queue_wait.observe(state.admit_t - state.arrival_t,
                                       tags)
        if state.preemptions:
            self._m_preemptions.inc(state.preemptions, tags)
        if state.first_token_t:
            self._m_ttft.observe(state.first_token_t - state.arrival_t,
                                 tags)
            if n_out > 1:
                self._m_tpot.observe(
                    (now - state.first_token_t) / (n_out - 1), tags)
            if tracing.tracing_enabled():
                # the request's life in the engine on the `llm` lane,
                # under the id the proxy minted (the `serve` lane's)
                wall = time.time() - time.perf_counter()
                for name, t0, t1 in (
                        ("queue", state.arrival_t, state.admit_t),
                        ("prefill", state.admit_t, state.first_token_t),
                        ("decode", state.first_token_t, now)):
                    tracing.record_lane_event(
                        "llm", name, wall + t0, wall + t1,
                        request_id=state.request_id)
        self._m_e2e.observe(now - state.arrival_t, tags)
        if state.cached_tokens:
            self._m_cache_hit.inc(state.cached_tokens, tags)
        self._m_prompt.inc(len(state.prompt), tags)
        if n_out:
            self._m_generated.inc(n_out, tags)

    async def _submit(self, prompt_ids: List[int],
                      params: SamplingParams,
                      model_id: Optional[str] = None):
        from ..serve.replica import current_request_id, current_tenant_id

        rid_in = current_request_id()
        if rid_in and (rid_in in self._queues
                       or rid_in in self.engine.requests):
            rid_in = None  # client reused an id mid-flight: don't collide
        rid = self.engine.add_request(prompt_ids, params,
                                      request_id=rid_in,
                                      model_id=model_id)
        tenant = current_tenant_id()
        if tenant:
            self._tenants[rid] = tenant
        q: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = q
        self._ensure_pump()
        return rid, q

    def _parse(self, payload: Dict[str, Any]):
        if "prompt_ids" in payload:
            prompt_ids = [int(t) for t in payload["prompt_ids"]]
        elif "prompt" in payload and self.tokenizer is not None:
            prompt_ids = self.tokenizer.encode(payload["prompt"])
        else:
            raise ValueError(
                "need 'prompt_ids' (or 'prompt' with a tokenizer configured)")
        from .sampling import SamplingParams

        params = SamplingParams(
            temperature=float(payload.get("temperature", 1.0)),
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 1.0)),
            max_tokens=int(payload.get("max_tokens", 64)),
            stop_token_ids=tuple(payload.get("stop_token_ids", ())))
        # OpenAI-style per-request "model": the server's base-model
        # name rides the base weights; any OTHER name must be a LOADED
        # LoRA adapter — an unknown name is a client error, not a
        # silent base-model fallback
        model_id = payload.get("model")
        if model_id is not None:
            if not isinstance(model_id, str):
                raise ValueError("'model' must be a string")
            if model_id in (self.model_name, "base", ""):
                model_id = None
            elif self.engine.lora_pool is None \
                    or model_id not in self.engine.lora_pool:
                loaded = (sorted(self.engine.lora_pool._slots)
                          if self.engine.lora_pool is not None else [])
                raise ValueError(
                    f"unknown model {model_id!r}: not this server's "
                    f"base model ({self.model_name!r}) or a loaded "
                    f"LoRA adapter ({loaded})")
        return prompt_ids, params, model_id

    def _detok(self, token_ids: List[int]) -> Optional[str]:
        if self.tokenizer is None:
            return None
        return self.tokenizer.decode(token_ids)

    # --- API methods (serve routes by method name; HTTP hits __call__) ---

    async def __call__(self, payload: Dict[str, Any]):
        """HTTP entry: chat if 'messages' present, else completions."""
        if isinstance(payload, dict) and "messages" in payload:
            return await self.chat(payload)
        return await self.completions(payload or {})

    async def completions(self, payload: Dict[str, Any]):
        """OpenAI-completions-shaped endpoint (ref: ray.llm's OpenAI
        router). ``stream=True`` returns an async generator serve turns
        into chunked HTTP (SSE-style ``data:`` lines)."""
        prompt_ids, params, model_id = self._parse(payload)
        if self._pool == "prefill" and self._decode_handle is not None:
            return await self._prefill_handoff(payload, prompt_ids,
                                               params, model_id)
        _rid, queue = await self._submit(prompt_ids, params, model_id)
        if payload.get("stream"):
            return self._stream_from(queue)
        tokens: List[int] = []
        finish_reason = None
        while True:
            out = await queue.get()
            tokens.append(out.token)
            if out.finished:
                finish_reason = out.finish_reason
                break
        body = {"object": "text_completion",
                "choices": [{"token_ids": tokens,
                             "finish_reason": finish_reason}]}
        text = self._detok(tokens)
        if text is not None:
            body["choices"][0]["text"] = text
        return body

    async def _stream_from(self, queue: asyncio.Queue):
        while True:
            out = await queue.get()
            chunk = {"token": out.token, "finished": out.finished}
            if out.finished:
                chunk["finish_reason"] = out.finish_reason
            yield f"data: {json.dumps(chunk)}\n\n"
            if out.finished:
                return

    # --- disaggregated prefill/decode (fleet KV plane) ---

    async def _prefill_handoff(self, payload: Dict[str, Any],
                               prompt_ids: List[int],
                               params: SamplingParams,
                               model_id: Optional[str]):
        """Prefill-pool request path: run the prompt pass here, export
        the sequence's KV pages, ship them to a decode replica
        (chunked object-store puts) and proxy its reply back. A failed
        handoff retries against another decode replica; after the
        retry budget it raises an attributed error — never a hang."""
        import time

        loop = asyncio.get_event_loop()
        rid, q = await self._submit(prompt_ids, params, model_id)
        first = await q.get()
        self._queues.pop(rid, None)
        if first.finished:
            # done at its first token (stop token / max_tokens=1):
            # nothing to hand off; the pump already observed the state
            tokens = [first.token]
            if payload.get("stream"):
                chunk = {"token": first.token, "finished": True,
                         "finish_reason": first.finish_reason}

                async def _one():
                    yield f"data: {json.dumps(chunk)}\n\n"

                return _one()
            body = {"object": "text_completion",
                    "choices": [{"token_ids": tokens,
                                 "finish_reason": first.finish_reason}]}
            text = self._detok(tokens)
            if text is not None:
                body["choices"][0]["text"] = text
            return body

        t0 = time.perf_counter()

        def _export():
            with self._engine_lock:
                return self.engine.export_kv_request(rid)

        handoff = await loop.run_in_executor(None, _export)
        # export finished the request outside step(), so the pump never
        # emits its terminal output: observe + drop the state here
        state = self.engine.requests.pop(rid, None)
        if state is not None:
            self._observe_finished(state, time.perf_counter())
        k = handoff.pop("k")
        v = handoff.pop("v")
        nbytes = int(k.nbytes) + int(v.nbytes)

        from .. import put
        from .._private import failpoints
        from .._private.config import global_config

        # ship pages in serve_kv_handoff_chunk_bytes slices so one huge
        # context doesn't materialize as a single giant object
        chunk_bytes = max(1, int(global_config().serve_kv_handoff_chunk_bytes))
        n_pages = int(k.shape[1])
        per_page = max(1, (nbytes // max(1, n_pages)))
        pages_per_chunk = max(1, chunk_bytes // per_page)

        def _ship():
            refs = []
            for s in range(0, n_pages, pages_per_chunk):
                e = min(n_pages, s + pages_per_chunk)
                refs.append(put((k[:, s:e], v[:, s:e])))
            return refs

        refs = await loop.run_in_executor(None, _ship)
        decode_payload = {
            "handoff": handoff,
            "kv_refs": refs,
            "sampling": {"temperature": params.temperature,
                         "top_k": params.top_k, "top_p": params.top_p,
                         "max_tokens": params.max_tokens,
                         "stop_token_ids": list(params.stop_token_ids)},
            "stream": bool(payload.get("stream")),
        }
        last_err: Optional[BaseException] = None
        result = replica = None
        for _attempt in range(3):
            try:
                await failpoints.afire("serve.kv_handoff",
                                       detail=self._dep_name or "")
                from ..serve.replica import current_tenant_id

                tenant = current_tenant_id()
                ref, replica = await loop.run_in_executor(
                    None, lambda: self._decode_handle.route(
                        decode_payload, request_id=rid,
                        tenant_id=tenant))
                result = await ref
                break
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — retried, then attributed
                last_err = e
                if self._m_handoff_retries is not None:
                    self._m_handoff_retries.inc()
                # a dead decode replica is the expected failure: force a
                # replica-set refresh so the retry lands elsewhere
                await loop.run_in_executor(
                    None, lambda: self._decode_handle._refresh(force=True))
        else:
            raise RuntimeError(
                f"KV handoff for request {rid} failed after 3 attempts "
                f"against the decode pool of deployment "
                f"{self._dep_name!r}; last error: {last_err!r}")
        if self._m_handoff_bytes is not None:
            self._m_handoff_bytes.inc(nbytes)
            self._m_handoff_lat.observe(time.perf_counter() - t0)
        if isinstance(result, dict) and "__stream__" in result:
            return self._proxy_stream(replica, result["__stream__"])
        return result

    async def _proxy_stream(self, replica, stream_id: int):
        """Relay a decode replica's response stream chunk by chunk
        (same pull protocol the HTTP proxy uses)."""
        from ..serve.replica import _STREAM_END

        finished = False
        try:
            while True:
                chunk = await replica.next_chunk.remote(stream_id)
                if isinstance(chunk, str) and chunk == _STREAM_END:
                    finished = True
                    return
                yield chunk
        finally:
            if not finished:
                try:
                    await replica.cancel_stream.remote(stream_id)
                except Exception:  # graftlint: ignore[swallow] — the
                    # decode replica may already be dead; releasing its
                    # generator is best-effort and the client's stream
                    # already ended either way
                    pass

    async def decode_from_kv(self, payload: Dict[str, Any]):
        """Decode-pool entry: pull the shipped KV chunks, inject them
        into this engine (no prompt pass) and generate the remaining
        tokens. Unusable payloads fall back to recomputing the prefill
        locally inside the engine — slower, never wrong."""
        import time

        import numpy as np

        from .. import get
        from ..serve.replica import current_request_id

        loop = asyncio.get_event_loop()
        meta = dict(payload["handoff"])
        refs = list(payload.get("kv_refs") or ())
        if refs:
            parts = await loop.run_in_executor(
                None, lambda: get(refs, timeout=120))
            ks = [p[0] for p in parts]
            meta["k"] = ks[0] if len(ks) == 1 else np.concatenate(
                ks, axis=1)
            vs = [p[1] for p in parts]
            meta["v"] = vs[0] if len(vs) == 1 else np.concatenate(
                vs, axis=1)
        s = payload.get("sampling") or {}
        from .sampling import SamplingParams

        params = SamplingParams(
            temperature=float(s.get("temperature", 1.0)),
            top_k=int(s.get("top_k", 0)),
            top_p=float(s.get("top_p", 1.0)),
            max_tokens=int(s.get("max_tokens", 64)),
            stop_token_ids=tuple(s.get("stop_token_ids", ())))
        rid_in = current_request_id()
        if rid_in and (rid_in in self._queues
                       or rid_in in self.engine.requests):
            rid_in = None

        def _inject():
            with self._engine_lock:
                return self.engine.inject_request(meta, params,
                                                  request_id=rid_in)

        rid = await loop.run_in_executor(None, _inject)
        from ..serve.replica import current_tenant_id

        tenant = current_tenant_id()
        if tenant:
            self._tenants[rid] = tenant
        pre = [int(t) for t in meta.get("output") or ()]
        state = self.engine.requests.get(rid)
        if state is not None and state.finished:
            # degenerate: already at its token budget after prefill —
            # finished inside inject, so no pump output will ever come
            self.engine.requests.pop(rid, None)
            self._observe_finished(state, time.perf_counter())
            if payload.get("stream"):
                return self._stream_decode(pre, None,
                                           state.finish_reason)
            body = {"object": "text_completion",
                    "choices": [{"token_ids": pre,
                                 "finish_reason": state.finish_reason}]}
            text = self._detok(pre)
            if text is not None:
                body["choices"][0]["text"] = text
            return body
        q: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = q
        self._ensure_pump()
        if payload.get("stream"):
            return self._stream_decode(pre, q)
        tokens = list(pre)
        finish_reason = None
        while True:
            out = await q.get()
            tokens.append(out.token)
            if out.finished:
                finish_reason = out.finish_reason
                break
        body = {"object": "text_completion",
                "choices": [{"token_ids": tokens,
                             "finish_reason": finish_reason}]}
        text = self._detok(tokens)
        if text is not None:
            body["choices"][0]["text"] = text
        return body

    async def _stream_decode(self, pre: List[int],
                             queue: Optional[asyncio.Queue],
                             finish_reason: Optional[str] = None):
        """Stream a decode-pool response: replay the prefill-side
        tokens first (the client never saw them), then live decode."""
        for i, t in enumerate(pre):
            last = queue is None and i == len(pre) - 1
            chunk: Dict[str, Any] = {"token": t, "finished": last}
            if last:
                chunk["finish_reason"] = finish_reason
            yield f"data: {json.dumps(chunk)}\n\n"
        if queue is not None:
            async for chunk_str in self._stream_from(queue):
                yield chunk_str

    async def chat(self, payload: Dict[str, Any]):
        """Chat-completions shim: template the messages through the
        tokenizer (requires one) then run completions."""
        if self.tokenizer is None:
            raise ValueError("chat endpoint requires a tokenizer")
        msgs = payload["messages"]
        prompt_ids = self.tokenizer.apply_chat_template(
            msgs, add_generation_prompt=True)
        body = dict(payload)
        body.pop("messages")
        body["prompt_ids"] = prompt_ids
        return await self.completions(body)

    async def stats(self, _payload=None) -> Dict[str, Any]:
        """The engine's ``stats()``, the replica's role, and the pump's
        wait for want of traffic: ``lull_s`` seconds without a request
        in ``lulls`` waits (the piece in progress, at most
        ``LULL_PIECE_S``, is not in yet)."""
        out = self.engine.stats()
        out["pool"] = self._pool
        out["pump"] = dict(self._pump_stats)
        return out

    @staticmethod
    def replica_actor_options() -> Dict[str, Any]:
        """What each replica asks of the cluster it is deployed to
        (serve.run): one chip, where some node has chips that its workers
        may take. The chip lease is what gives the replica the TPU
        backend (_private/device_plane.py), and a replica that holds a
        chip refuses to serve from any other backend. A cluster held to
        the CPU (``JAX_PLATFORMS=cpu``, the test suite) serves from the
        CPU whatever device nodes its hosts show."""
        from .. import nodes
        from .._private import device_plane

        for node in nodes():
            if (node["Alive"] and node["Resources"].get("TPU", 0.0) >= 1
                    and device_plane.allows_tpu(node["Labels"].get(
                        device_plane.JAX_PLATFORMS_LABEL, ""))):
                return {"num_tpus": 1}
        return {}

    async def device_info(self, payload: Optional[dict] = None
                          ) -> Dict[str, Any]:
        """What this replica really runs on, as jax reports it: nothing
        here is inferred from the lease. With ``{"prompt_len": n}``,
        ``prefill_attention`` says what the prefill program for a prompt
        of that length was compiled to: ``"pallas"`` where its text holds
        the flash kernels' ``tpu_custom_call``s, else ``"blockwise"``
        (chunked prefill attends with plain einsums and compiles no
        whole-prompt program)."""
        import jax

        from .. import get_tpu_chip_ids
        from .._private import device_plane

        prefill_attention = None
        prompt_len = (payload or {}).get("prompt_len")
        if prompt_len is not None and self.engine.ecfg.prefill_chunk > 0:
            prefill_attention = {"path": "einsum (chunked prefill)"}
        elif prompt_len is not None:
            # compiling must not stall the replica's event loop
            bucket, compiled = await asyncio.get_event_loop().run_in_executor(
                None, self.engine.compile_prefill, int(prompt_len))
            calls = compiled.as_text().count("tpu_custom_call")
            prefill_attention = {
                "bucket": bucket, "tpu_custom_calls": calls,
                "path": "pallas" if calls else "blockwise"}
        dev = self._device
        return {
            "pid": os.getpid(),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_id": dev.id,
            "device_count": len(jax.devices()),
            "chip_ids": get_tpu_chip_ids(),
            "memory_stats": dev.memory_stats(),
            "prefill_attention": prefill_attention,
            "compile_cache_dir": self._compile_cache_dir,
            "compile_cache": device_plane.compilation_cache_stats(),
        }


def build_llm_deployment(model="tiny", *, num_replicas: int = 1,
                         name: str = "llm",
                         pools: Optional[dict] = None, **server_kwargs):
    """An Application running LLMServer replicas (ref: ray.llm
    build_openai_app). ``model`` is a name in ``LLAMA_CONFIGS``, an HF
    checkpoint directory, or a ``LlamaConfig`` itself. ``pools={"prefill": n, "decode": m}`` deploys
    disaggregated prefill/decode pools instead of ``num_replicas``
    monolithic replicas (fleet KV plane).

    Each replica is a process of its own and, on a cluster whose nodes
    have TPU chips, holds one of them: ``serve.run`` asks
    ``LLMServer.replica_actor_options`` once the cluster is known."""
    from .. import serve

    dep = serve.deployment(LLMServer, name=name,
                           num_replicas=num_replicas,
                           pools=pools)
    return dep.bind(model, **server_kwargs)
