"""LLMEngine: continuous batching over the paged-KV model runner.

Reference analog: the vLLM engine loop ray.llm wraps
(llm/_internal/serve/deployments/llm/vllm/vllm_engine.py) — request
queue -> schedule -> {prefill | decode} -> sample -> stream. Rebuilt
TPU-first:

  * decode batch has a FIXED width (``max_num_seqs`` slots) and a
    burst's steps are an operand, so a decode executable a page-list
    bucket (a handful: ``decode_buckets``) serves the engine's whole
    lifetime: continuous batching = host-side slot assignment and the
    list of the live pages, not shape changes;
  * prefills are bucketed (power-of-2 padding, and the rungs between
    that ``runner.PREFILL_RUNGS`` names) and run one request per
    step between decode steps (chunked-prefill-lite: bounded TTFT impact
    on running streams);
  * all paging is host-side (PageAllocator); the device never sees an
    allocation decision, only block tables;
  * what the cache's arrays hold is the configuration's cache kind's
    affair (``llm/kinds``); the engine asks the kind (``self.kind``) what
    a burst lists, which counters its queries move, which path its
    attention takes and which features it refuses (``_refuse``);
  * layers whose keys live equally long share a pool, an allocator and a
    table (``kinds/paged.py``, "Layer groups"): a configuration of full
    and window layers has two of each, a request is admitted when every
    group can hold it, and a window group's sequence gives its pages
    back as they leave the window (``_release``), after its prefill and
    after every burst. With one kind of layer there is one group:
    ``allocator``, ``seq_table`` and ``cache`` are its.

The engine is synchronous and single-threaded by design — an actor wraps
it for serving (ray_tpu.llm.serve) the way vLLM's AsyncLLMEngine wraps
its LLMEngine.
"""

from __future__ import annotations

import collections
import itertools
import logging
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig
from ..ops import rope_frequencies
from ..ops.attention import attention_path
from ..ops.moe import chosen_tiles
from ..util import tracing
from . import kinds
from .cache import (KVCache, PageAllocator, PrefixCache, SequenceTable,
                    init_kv_cache, window_group_pages, zero_slot_state)
from .runner import (PREFILL_RUNGS, decode_burst, page_bucket,
                     prefill_bucket, prefill_sample, verify_step)
from .sampling import SamplingParams


logger = logging.getLogger(__name__)


@dataclass
class EngineConfig:
    max_num_seqs: int = 8           # decode slots (static batch width)
    page_size: int = 16
    # incl. the reserved page 0. Of the layers that see the whole
    # sequence; a window group's pool is sized by what can be live in
    # it: cache.window_group_pages(slots, window, page, burst)
    num_pages: int = 512
    max_seq_len: int = 2048
    kv_dtype: Any = None            # default: model dtype
    # decode steps fused into one device dispatch (multi-step
    # scheduling); >1 amortizes host->device round trips at the cost of
    # up to burst-1 wasted tokens past a stop token
    decode_burst: int = 8
    # chunked prefill (vLLM --enable-chunked-prefill analog): process
    # prompts in chunks of this many tokens, interleaving decode bursts
    # between chunks so a long prompt doesn't stall running streams for
    # its whole prefill; also one compiled executable per (chunk, span)
    # instead of per pow-2 prompt bucket. What it buys a running stream
    # and costs the long prompt: not measured (no cell runs it, ROADMAP
    # S10). Chunked vs whole-prompt logits agree to bf16 precision
    # (argmax/top-5 identical; greedy token streams may diverge after
    # many steps, as between any two correct bf16 attention
    # implementations). 0 = whole-prompt.
    prefill_chunk: int = 0
    # finished RequestStates kept for inspection before FIFO eviction
    # (callers that stream from step() outputs never need them)
    finished_retention: int = 1024
    # multi-LoRA serving (vLLM --enable-lora analog, S-LoRA batched
    # adapters — llm/lora.py): rank > 0 builds a static adapter pool;
    # requests carry model_id and every slot in a decode batch can wear
    # a different adapter. Incompatible with prefix caching (cached
    # pages would mix adapters) and chunked prefill for now.
    lora_rank: int = 0
    max_loras: int = 8
    # speculative decoding (llm/spec_decode.py — Leviathan et al.): a
    # dict {"draft_config": ..., "num_draft_tokens": k} or SpecConfig.
    # Greedy requests get k draft tokens verified per round in one
    # batched forward; output stays token-identical to plain greedy
    # decode. None = off. Incompatible with lora_rank > 0.
    speculation: Any = None
    # automatic prefix caching (vLLM --enable-prefix-caching analog):
    # full prompt pages are content-addressed and SHARED across
    # sequences via page refcounts; a request whose prompt prefix is
    # cached skips that prefix's prefill compute entirely (chunked
    # prefill starts past it). Forces chunked-prefill mode.
    enable_prefix_caching: bool = False


@dataclass
class RequestState:
    request_id: str
    prompt: List[int]
    params: SamplingParams
    output: List[int] = field(default_factory=list)
    slot: int = -1
    ctx_len: int = 0          # 0 until prefill completes
    prefill_pos: int = 0      # chunked prefill progress (tokens written)
    prompt_page_keys: Any = None   # prefix-cache keys (full pages)
    cached_tokens: int = 0         # prefix tokens served from the cache
    model_id: Optional[str] = None # LoRA adapter name (None = base)
    finished: bool = False
    finish_reason: Optional[str] = None
    # perf_counter clocks, 0.0 until reached; each is set once, so
    # arrival_t <= admit_t <= prefill_start_t <= first_token_t <= emit_t:
    # queue wait, wait for the prefill turn, prefill, hand-over
    arrival_t: float = 0.0
    admit_t: float = 0.0          # first slot + pages (_admit)
    prefill_start_t: float = 0.0  # first prefill work unit began
    first_token_t: float = 0.0
    emit_t: float = 0.0           # first StepOutput queued (LLMServer._pump)
    preemptions: int = 0


# the phases that partition a round: the last parts of the span names
# (rt.engine.<phase>) and the keys of stats()["counters"]["host_s"].
# A dispatch is two of them: ``build`` is what the host makes before it
# can call (shapes, pages, the numpy rows, the sampling arrays),
# ``dispatch`` the jitted call with the uploads in its arguments. A
# speculative round has ``decode.draft`` before them: the window's
# pages and the drafter's own calls of the device. Each decode name is
# opened at most once a round.
PHASES = ("schedule", "prefill.build", "prefill.dispatch", "prefill.sync",
          "decode.draft", "decode.build", "decode.dispatch", "release",
          "decode.sync", "append")
_SPAN_NAMES = {phase: "rt.engine." + phase for phase in PHASES}


@dataclass
class StepOutput:
    request_id: str
    token: int
    finished: bool
    finish_reason: Optional[str] = None
    text_offset: int = 0


def burst_gather(tables: np.ndarray, page_size: int, bucket: int,
                 held) -> np.ndarray:
    """``runner.decode_burst``'s ``gather`` of one layer group, int32
    [3, bucket]: ONE list of the pages that hold old context of the
    decoding slots, slot after slot, each with its (page, owner slot,
    first position); the rest is owned by nobody (-1). ``tables``: the
    group's block tables [B, max_pages]; ``held``: (slot, pages that
    hold its old context) of those slots, with a third number where the
    first of them is not the table's entry 0 (a window group's)."""
    out = np.zeros((3, bucket), np.int32)
    out[1] = -1
    at = 0
    for slot, n, *first in held:
        first = first[0] if first else 0
        out[0, at:at + n] = tables[slot, first:first + n]
        out[1, at:at + n] = slot
        out[2, at:at + n] = (first + np.arange(n)) * page_size
        at += n
    return out



def burst_rows(tables: np.ndarray, page_size: int, width: int, held):
    """``runner.decode_burst``'s ``gather`` of a layer group whose burst
    gathers a row a slot (``kinds.ROWS``): (first int32 [B], pages int32
    [B, width]): a decoding slot's pages that hold old context and the
    position its first one begins at; page 0 elsewhere. ``tables`` and
    ``held`` as ``burst_gather``'s."""
    first = np.zeros(tables.shape[0], np.int32)
    pages = np.zeros((tables.shape[0], width), np.int32)
    for slot, n, *at in held:
        at = at[0] if at else 0
        first[slot] = at * page_size
        pages[slot, :n] = tables[slot, at:at + n]
    return first, pages


class LLMEngine:
    def __init__(self, params, cfg: LlamaConfig,
                 engine_config: Optional[EngineConfig] = None):
        self.cfg = cfg
        self.ecfg = engine_config or EngineConfig()
        if self.ecfg.max_seq_len > cfg.max_seq:
            raise ValueError("engine max_seq_len exceeds model max_seq")
        usable = self.ecfg.num_pages - 1  # page 0 is reserved
        need = -(-self.ecfg.max_seq_len // self.ecfg.page_size)
        if need > usable:
            # guarantees a lone running sequence can always grow to
            # max_seq_len, which keeps preemption deadlock-free
            raise ValueError(
                f"num_pages={self.ecfg.num_pages} cannot hold one "
                f"max_seq_len={self.ecfg.max_seq_len} sequence "
                f"({need} pages needed, {usable} usable)")
        self.params = params
        # the layer groups (kinds/paged.py): their windows, names, pools
        self.windows = cfg.kv_groups
        self.group_names = ["full" if w is None else "window"
                            for w in self.windows]
        grouped = len(self.windows) > 1
        if self.ecfg.enable_prefix_caching:
            self._refuse("enable_prefix_caching")
        if self.ecfg.lora_rank > 0:
            self._refuse("lora_rank")
        pool_pages = [
            self.ecfg.num_pages if w is None else window_group_pages(
                self.ecfg.max_num_seqs, w, self.ecfg.page_size,
                self.ecfg.decode_burst) for w in self.windows]
        self.cache = init_kv_cache(
            cfg, pool_pages if grouped else pool_pages[0],
            self.ecfg.page_size, self.ecfg.kv_dtype,
            slots=self.ecfg.max_num_seqs)
        self.allocators = [PageAllocator(n, self.ecfg.page_size)
                           for n in pool_pages]
        self.allocator = self.allocators[0]
        self.lora_pool = None
        if self.ecfg.lora_rank > 0:
            from .lora import LoRAPool

            if self.ecfg.enable_prefix_caching:
                raise ValueError(
                    "lora_rank and enable_prefix_caching are mutually "
                    "exclusive (cached pages would mix adapters)")
            if self.ecfg.prefill_chunk > 0:
                raise ValueError(
                    "lora_rank requires whole-prompt prefill "
                    "(prefill_chunk=0) for now")
            self.lora_pool = LoRAPool(cfg, self.ecfg.lora_rank,
                                      self.ecfg.max_loras,
                                      dtype=cfg.dtype)
        self.prefix_cache: Optional[PrefixCache] = None
        if self.ecfg.enable_prefix_caching:
            self.prefix_cache = PrefixCache(self.allocator)
            if self.ecfg.prefill_chunk <= 0:
                # cached-prefix requests resume mid-prompt, which is the
                # chunked runner's contract. COPY before adjusting — the
                # caller's config object must not mutate under it.
                import dataclasses as _dc

                self.ecfg = _dc.replace(
                    self.ecfg,
                    prefill_chunk=min(512, self.ecfg.max_seq_len))
        max_pages = self.allocator.pages_needed(self.ecfg.max_seq_len)
        if grouped and self.ecfg.prefill_chunk > 0 and any(
                a.num_pages - 1 < max_pages for a in self.allocators):
            # a chunked prefill holds every page of its prompt until the
            # prompt ends (release between chunks: ROADMAP M4)
            raise ValueError(
                f"prefill_chunk with layer groups holds a whole prompt's "
                f"pages in every group until it ends: a group's pool is "
                f"smaller than one max_seq_len={self.ecfg.max_seq_len} "
                f"sequence ({max_pages} pages)")
        self.seq_tables = [SequenceTable(self.ecfg.max_num_seqs, max_pages)
                           for _ in self.windows]
        self.seq_table = self.seq_tables[0]
        cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq,
                                    cfg.rope_theta,
                                    scaling=cfg.rope_scaling)
        self.cos, self.sin = jax.device_put(cos), jax.device_put(sin)
        # speculative decoding (drafter + verify window; spec_decode.py)
        self.spec = None
        # fleet verify hook (llm/serve.py): (payload, draft) ->
        # Optional[List[int]] — ships a KV snapshot to a prefill-class
        # verifier racing the local verify; None/exception = local only
        self._spec_remote_verify = None
        if self.ecfg.speculation:
            self.enable_speculation(self.ecfg.speculation)
        self.waiting: Deque[RequestState] = collections.deque()
        # admitted (slot+pages held) but not yet fully prefilled; one
        # prefill work unit runs per step — a whole prompt, or one chunk
        self._prefill_queue: Deque[RequestState] = collections.deque()
        self._prefill_skips: Dict[str, int] = {}  # SRF aging counters
        self.slots: List[Optional[RequestState]] = (
            [None] * self.ecfg.max_num_seqs)
        self.requests: Dict[str, RequestState] = {}
        self._finished_order: Deque[str] = collections.deque()
        self._seed = 0
        self._id = itertools.count()
        # device-side block-table cache, refreshed only when the host
        # table mutates (saves one H2D upload per decode step)
        self._bt_device = None
        self._bt_version = -1
        self._tables_device = None
        self._tables_version = None
        # always-on round counters (stats()["counters"]): an integer add
        # or a clock difference each, no allocation per token. Decode
        # rounds are the plain bursts (speculative rounds count in
        # stats()["spec"]); a prefill is one dispatch (a whole prompt,
        # or one chunk); host_s is by phase, from the spans' clocks.
        self._counters: Dict[str, Any] = {
            "rounds": 0, "decode_steps": 0,
            "width_hist": [0] * (self.ecfg.decode_burst + 1),
            "active_slot_steps": 0, "prefills": 0, "prefill_tokens": 0,
            # the rows those tokens were padded to (a whole prompt's
            # bucket, a chunk's width): tokens over these is the share
            # of prefill's rows that are tokens
            "prefill_bucket_tokens": 0,
            "preemptions": 0, "host_s": dict.fromkeys(PHASES, 0.0),
            # what ``load_decode_programs`` and ``load_prefill_programs``
            # did before the replica was ready: the programs they ran
            # once, and the seconds that took (trace, lowering, compile
            # or the cache's read, one run)
            "loaded_programs": 0, "load_s": 0.0,
            # the bursts' page lists, summed over rounds: pages that hold
            # old context of decoding slots, pages the burst copied
            # (their ratio: the share of the copy that was needed), and
            # the rounds by the list's bucket
            "live_pages": 0, "gathered_pages": 0, "gather_hist": {},
            # the same a layer group, with the pages its sequences gave
            # back behind the window and the admissions put off because
            # this group had too few free pages
            "groups": {name: {"live_pages": 0, "gathered_pages": 0,
                              "released_pages": 0,
                              "deferred_admissions": 0}
                       for name in self.group_names}}
        if cfg.n_experts:
            # counted on the device by the routed layer, summed over
            # layers and programs: the (token, expert) rows the expert
            # products were given, and the experts with at least one row
            self._counters.update(expert_rows=0, experts_touched=0)
            if cfg.experts_held is not None:
                # tokens' rows the router gave to experts that are not
                # on this chip (one chip's share of the experts)
                self._counters["expert_rows_elsewhere"] = 0
        # the kind's own, which its ``count`` moves
        self._counters.update(dict.fromkeys(self.kind.COUNTERS, 0))
        # what one cached position holds, all layers (a position's share
        # of its page's sums of strides among it)
        self._counters["kv_bytes_per_token"] = int(sum(
            a.size // (a.shape[1] * self.ecfg.page_size) * a.dtype.itemsize
            for a in jax.tree.leaves(
                (self.cache.k, self.cache.v, self.cache.i, self.cache.c))))
        # expert counts of chunked-prefill dispatches nobody waited for
        # yet: read back with the next sampled tokens
        self._pending_counts: List[Any] = []
        paths = self.attention_paths()
        logger.log(
            logging.WARNING if jax.default_backend() == "tpu"
            and "blockwise" in paths["prefill"] else logging.INFO,
            "attention paths (heads of %d, values of %d): %s",
            cfg.head_dim, cfg.value_dim, paths)

    @property
    def kind(self):
        """The configuration's cache kind (``llm/kinds``)."""
        return kinds.of(self.cfg)

    def attention_paths(self) -> Dict[str, str]:
        """Which implementation each program's attention takes on this
        backend (``ops.attention.attention_path`` at the largest prefill
        bucket, and the kind's account): logged once when the engine is
        made (a warning where a TPU's whole-prompt prefill falls to plain
        jax: a head size the flash kernels cannot tile). What was really
        compiled is in the program's text (``compile_prefill``)."""
        on_tpu = jax.default_backend() == "tpu"
        top = self._prefill_rows(self.ecfg.max_seq_len)
        return self.kind.attention_paths(self.cfg, attention_path(
            top, top, self.cfg.head_dim, on_tpu, self.cfg.value_dim), on_tpu)

    def _refuse(self, feature: str, what: Optional[str] = None) -> None:
        """What the configuration's cache kind cannot do yet, refused by
        the option's name (or ``what``: the hand-over call's) before
        anything is built: THE reader of the kinds' ``refuses`` tables."""
        named, reasons = self.kind.refuses(self.cfg)
        if feature in reasons:
            raise ValueError(
                f"{what or 'EngineConfig.' + feature} is not supported "
                f"with {named}: {reasons[feature]}")

    def _count(self, start: int, end: int, decode: bool = False) -> None:
        """The queries at positions [start, end) of one sequence, for the
        kind's counters (``decode``: a burst's steps)."""
        self.kind.count(self.cfg, self._counters, self.ecfg.page_size,
                        start, end, decode)

    def _run(self, program, *args, **kwargs):
        """A runner program on this engine's pools, which come back as
        the cache: (what the program returns before its pools ..., its
        expert counts)."""
        cache = self.cache
        # the kind's other pools: keywords in, behind the counts out
        more = {name: pool for name, pool in (
            ("cache_i", cache.i), ("cache_c", cache.c), ("cache_s", cache.s))
            if pool is not None}
        out = list(program(self.params, cache.k, cache.v, *args, **more,
                           **kwargs))
        back = dict(zip(more, out[len(out) - len(more):]))
        *out, cache_k, cache_v, counts = out[:len(out) - len(more)]
        self.cache = KVCache(cache_k, cache_v, back.get("cache_i"),
                             back.get("cache_c"), back.get("cache_s"))
        return (*out, counts)

    def _read_back(self, toks, counts=None):
        """The sampled tokens on the host (the round's one sync). An
        expert config's counts ride the same ``device_get``: outputs of
        the program the tokens come from, so nothing more is waited
        for."""
        if counts is None and not self._pending_counts:
            return np.asarray(toks)
        pending, self._pending_counts = self._pending_counts, []
        if counts is not None:
            pending.append(counts)
        sampled, pending = jax.device_get((toks, pending))
        for rows, touched, *elsewhere in pending:
            self._counters["expert_rows"] += int(rows)
            self._counters["experts_touched"] += int(touched)
            if elsewhere and "expert_rows_elsewhere" in self._counters:
                self._counters["expert_rows_elsewhere"] += int(elsewhere[0])
        return sampled

    @contextmanager
    def _phase(self, phase: str):
        """One phase of the round: a span on the profiler's clock, and
        the same interval added to ``host_s``."""
        with tracing.span(_SPAN_NAMES[phase]) as sp:
            yield
        self._counters["host_s"][phase] += sp.seconds

    # --- public API ---

    def add_request(self, prompt_tokens: List[int],
                    params: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None,
                    model_id: Optional[str] = None) -> str:
        if not prompt_tokens:
            raise ValueError("empty prompt")
        if model_id is not None:
            if self.lora_pool is None:
                raise ValueError("model_id requires EngineConfig."
                                 "lora_rank > 0")
            self.lora_pool.slot_of(model_id)   # validate at submission
        if len(prompt_tokens) >= self.ecfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt_tokens)} >= max_seq_len "
                f"{self.ecfg.max_seq_len}")
        rid = request_id or f"req-{next(self._id)}"
        state = RequestState(rid, list(prompt_tokens),
                             params or SamplingParams(),
                             arrival_t=time.perf_counter(),
                             model_id=model_id)
        self.waiting.append(state)
        self.requests[rid] = state
        return rid

    def enable_speculation(self, spec, draft_params=None) -> None:
        """Attach a drafter (spec_decode.SpecDecoder). ``spec`` is the
        ``speculation`` dict/SpecConfig; ``draft_params`` overrides the
        drafter's random init (a trained 400m draft checkpoint)."""
        from .spec_decode import SpecDecoder

        self._refuse("speculation")
        if self.lora_pool is not None:
            raise ValueError("speculation is incompatible with "
                             "lora_rank > 0 (drafter has no adapters)")
        self.spec = SpecDecoder(self.cfg, self.ecfg, spec,
                                draft_params=draft_params)

    def disable_speculation(self) -> None:
        self.spec = None

    def abort_request(self, request_id: str) -> None:
        state = self.requests.get(request_id)
        if state is None or state.finished:
            return
        self._finish(state, "aborted")

    def has_unfinished(self) -> bool:
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def step(self, skip_decode: bool = False) -> List[StepOutput]:
        """One scheduling round: admit waiting requests into free slots
        (host-side bookkeeping only), advance ONE prefill work unit (a
        whole prompt, or one chunk of one prompt), then one batched
        decode burst for every decoding slot. ``skip_decode`` runs only
        the admission/prefill phase (TTFT measurement, draining a
        prefill backlog before decoding)."""
        outputs: List[StepOutput] = []
        # The phases below are siblings; no span covers the whole round
        # (it would name every device idle gap in the round after
        # itself): the round's total goes to the JSONL sink alone
        wall0 = time.time() if tracing.tracing_enabled() else 0.0
        with self._phase("schedule"):
            # purge stale entries (aborted/preempted mid-queue) FIRST:
            # they must neither count toward the admission cap nor linger
            if any(s.slot < 0 or s.finished for s in self._prefill_queue):
                self._prefill_queue = collections.deque(
                    s for s in self._prefill_queue
                    if s.slot >= 0 and not s.finished)
            # admission never blocks on prefill, but the queue is capped:
            # admission reserves the WHOLE sequence's pages, so admitting
            # every waiting request up front would pin pages that running
            # streams need (recompute-preemption cost). Whole-prompt mode
            # caps at 1 — exactly the old admit-and-prefill-per-step pace.
            cap = 1 if self.ecfg.prefill_chunk <= 0 else 2
            while len(self._prefill_queue) < cap:
                admitted = self._admit()
                if admitted is None:
                    break
                self._prefill_queue.append(admitted)
            pref = self._next_prefill()
        if pref is not None:
            outputs.extend(self._run_prefill(pref))
            if pref.ctx_len > 0 or pref.slot < 0 or pref.finished:
                # done (or preempted/aborted meanwhile): leave the queue
                try:
                    self._prefill_queue.remove(pref)
                except ValueError:
                    pass
        if not skip_decode and any(
                s is not None and s.ctx_len > 0 for s in self.slots):
            outputs.extend(self._run_decode())
        if wall0:
            tracing.record_lane_event("engine", "rt.engine.round", wall0,
                                      time.time())
        return outputs

    # consecutive work units a queued prefill may be passed over before
    # it runs regardless of length (anti-starvation aging for SRF)
    _PREFILL_MAX_SKIPS = 8

    def _next_prefill(self) -> Optional[RequestState]:
        """Pick this round's prefill work unit. Whole-prompt mode keeps
        FIFO order. Chunked mode picks the request with the FEWEST
        remaining prefill tokens (arrival-order tiebreak) — a short
        prompt admitted behind a long one starts streaming after its own
        chunk count — with aging: the oldest queued request runs after
        at most _PREFILL_MAX_SKIPS pass-overs, so a sustained stream of
        short prompts cannot starve a long one indefinitely."""
        while self._prefill_queue and (
                self._prefill_queue[0].slot < 0
                or self._prefill_queue[0].finished):
            self._prefill_queue.popleft()  # preempted/aborted
        live = [s for s in self._prefill_queue
                if s.slot >= 0 and not s.finished]
        # aging counters live exactly as long as their queue entry
        # (aborted/preempted requests must not leak entries)
        live_ids = {s.request_id for s in live}
        for rid in [r for r in self._prefill_skips if r not in live_ids]:
            del self._prefill_skips[rid]
        if not live:
            return None
        if self.ecfg.prefill_chunk <= 0:
            return live[0]
        oldest = min(live, key=lambda s: s.arrival_t)
        if self._prefill_skips.get(oldest.request_id, 0) \
                >= self._PREFILL_MAX_SKIPS:
            pick = oldest
        else:
            pick = min(live, key=lambda s: (
                len(s.prompt) + len(s.output) - s.prefill_pos,
                s.arrival_t))
        for s in live:
            if s is pick:
                self._prefill_skips.pop(s.request_id, None)
            else:
                self._prefill_skips[s.request_id] = (
                    self._prefill_skips.get(s.request_id, 0) + 1)
        return pick

    def generate(self, prompts: List[List[int]],
                 params: Optional[SamplingParams] = None) -> List[List[int]]:
        """Batch entry point: run all prompts to completion. Outputs are
        collected from step() results, so batches larger than the
        finished-request retention window work fine."""
        ids = [self.add_request(p, params) for p in prompts]
        collected: Dict[str, List[int]] = {rid: [] for rid in ids}
        while self.has_unfinished():
            for out in self.step():
                if out.request_id in collected:
                    collected[out.request_id].append(out.token)
        return [collected[rid] for rid in ids]

    # --- scheduling internals ---

    def _free_slot(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return -1

    def _admit(self) -> Optional[RequestState]:
        if not self.waiting:
            return None
        slot = self._free_slot()
        if slot < 0:
            return None
        state = self.waiting[0]
        # pages for the whole sequence so far (prompt + any tokens
        # generated before a preemption) + the next generated token
        seq_len = len(state.prompt) + len(state.output)
        cached_pages: List[int] = []
        if self.prefix_cache is not None:
            if state.prompt_page_keys is None:
                state.prompt_page_keys = PrefixCache.page_keys(
                    state.prompt, self.ecfg.page_size)
            hits = self.prefix_cache.lookup(state.prompt_page_keys)
            # at least one prompt token must run through prefill (its
            # logits seed sampling): never cache the WHOLE prompt
            cap = (len(state.prompt) - 1) // self.ecfg.page_size
            while len(hits) > cap:
                self.allocator.free([hits.pop()])
            cached_pages = hits
        fresh_tokens = seq_len + 1 - len(cached_pages) * self.ecfg.page_size
        # a window group holds the pages from the one the first key the
        # next query sees lies on (a chunked prefill fills, and holds,
        # them all until the prompt ends)
        firsts = [0 if w is None or self.ecfg.prefill_chunk > 0
                  else self._first_live_page(seq_len, w)
                  for w in self.windows]
        end = self.allocator.pages_needed(seq_len + 1)
        short = [name for name, allocator, first in list(zip(
            self.group_names, self.allocators, firsts))[1:]
            if allocator.free_pages < end - first]
        if short:
            for name in short:
                self._counters["groups"][name]["deferred_admissions"] += 1
            if cached_pages:
                self.allocator.free(cached_pages)
            return None
        if not self.allocator.can_allocate(fresh_tokens):
            if self.prefix_cache is not None:
                need = self.allocator.pages_needed(fresh_tokens)
                # only sacrifice cached prefixes when eviction can
                # actually enable THIS admission
                if (self.allocator.free_pages
                        + self.prefix_cache.evictable()) >= need:
                    self.prefix_cache.evict_for(fresh_tokens)
            if not self.allocator.can_allocate(fresh_tokens):
                if cached_pages:
                    self.allocator.free(cached_pages)
                self._counters["groups"][self.group_names[0]][
                    "deferred_admissions"] += 1
                return None
        self.waiting.popleft()
        pages = cached_pages + self.allocator.allocate(
            self.allocator.pages_needed(fresh_tokens))
        state.slot = slot
        state.cached_tokens = len(cached_pages) * self.ecfg.page_size
        state.prefill_pos = state.cached_tokens
        if not state.admit_t:
            state.admit_t = time.perf_counter()
        self.slots[slot] = state
        if self.cache.s is not None:
            # the slot's state of every state layer starts from zero
            # (a resumed request too: it prefills again)
            self.cache.s = zero_slot_state(self.cache.s, jnp.int32(slot))
            self._counters[self.kind.SLOT_RESET] += 1
        self.seq_table.assign(slot, pages)
        for allocator, table, first in list(zip(
                self.allocators, self.seq_tables, firsts))[1:]:
            table.assign(slot, allocator.allocate(end - first), first)
        return state

    def _first_live_page(self, ctx_len: int, window: int) -> int:
        """The table entry of the oldest key a window layer's next query
        (at position ``ctx_len``) sees: every page before it has left the
        window."""
        return max(ctx_len - window + 1, 0) // self.ecfg.page_size

    def _release(self, state: RequestState) -> None:
        """Give back the pages of a decoding sequence's window groups
        that have left the window (reference counts as they are: a page
        something else holds survives)."""
        for g, window in enumerate(self.windows):
            if window is None or state.slot < 0:
                continue
            pages = self.seq_tables[g].release_front(
                state.slot, self._first_live_page(state.ctx_len, window))
            if pages:
                self.allocators[g].free(pages)
                self._counters["groups"][self.group_names[g]][
                    "released_pages"] += len(pages)

    # smallest block-table span bucket, in pages: a rectangle of a row
    # a slot (a speculative round) reads the longest ACTIVE context's
    # bucket, not max_seq_len
    _SPAN_PAGES = 4

    def _bt(self, span: Optional[int] = None):
        key = (self.seq_table.version, span)
        if self._bt_version != key:
            table = self.seq_table.block_tables
            if span is not None:
                table = table[:, :span]
            self._bt_device = jnp.asarray(table)
            self._bt_version = key
        return self._bt_device

    def _tables(self):
        """The block tables on the device as the programs take them: the
        one group's, or a tuple with one a group."""
        key = tuple(t.version for t in self.seq_tables)
        if self._tables_version != key:
            self._tables_device = tuple(
                jnp.asarray(t.block_tables) for t in self.seq_tables)
            self._tables_version = key
        tables = self._tables_device
        return tables if len(tables) > 1 else tables[0]

    def _rows(self, slot: int):
        """One slot's rows of the block tables, as ``_tables``."""
        rows = tuple(jnp.asarray(t.block_tables[slot:slot + 1])
                     for t in self.seq_tables)
        return rows if len(rows) > 1 else rows[0]

    def _span_bucket(self, pages: int) -> int:
        """Power-of-2 page-span bucket, capped at the table width."""
        return page_bucket(pages, self.seq_table.block_tables.shape[1],
                           self._SPAN_PAGES)

    def _active_span(self) -> int:
        """Pages covering the longest DECODING sequence, bucketed (a
        speculative round's table: its window is written through it).
        Mid-prefill slots (ctx_len 0) hold their full page allocation up
        front: counting them would balloon the round's KV gather to the
        long prompt's whole table."""
        longest = max((int(self.seq_table.n_pages[s.slot])
                       for s in self.slots
                       if s is not None and s.ctx_len > 0), default=1)
        return self._span_bucket(longest)

    # --- the burst's page list (``burst_gather``) ---

    # smallest buckets, in pages, of a flat list and of a burst's table
    # span where it reads each slot's own pages: the kinds say why
    _FLAT_PAGES = kinds.paged.LOWEST_BUCKET
    _LATENT_SPAN_PAGES = kinds.latent.LOWEST_BUCKET

    def _slot_pages(self, g: int):
        """(fewest, most) pages of group ``g`` that can hold old context
        of one long decoding slot: the table's width, or for a window
        group the window's pages, one more where its edges fall inside
        pages."""
        top, window = self.seq_table.block_tables.shape[1], self.windows[g]
        if window is None:
            return top, top
        page = self.ecfg.page_size
        return min(top, window // page), min(top, -(-window // page) + 1)

    def _listable_pages(self, g: int = 0) -> int:
        """The most pages a flat list of group ``g`` can hold: every
        slot's whole table (or window), or the pool where no page is
        shared (a page two slots share, the prefix cache's, is listed
        once for each)."""
        B = self.seq_table.block_tables.shape[0]
        most = B * self._slot_pages(g)[1]
        if self.prefix_cache is not None:
            return most
        return min(most, self.allocators[g].num_pages - 1)

    def _flat_bucket(self, pages: int, g: int = 0) -> int:
        """Power-of-2 bucket of a flat list, capped at what can be
        listed (a pool of 384 pages tops out there, not at 512)."""
        return page_bucket(pages, self._listable_pages(g),
                           self._FLAT_PAGES)

    def _latent_span(self, pages: int) -> int:
        """Power-of-2 bucket of the block tables (pages a slot) of a
        burst that reads each slot's own pages, capped at the table's
        width."""
        return page_bucket(pages, self.seq_table.block_tables.shape[1],
                           self._LATENT_SPAN_PAGES)

    def _own_pages(self, g: int = 0):
        """How a burst reads group ``g``'s pages (the kind's
        ``OWN_PAGES``: one fact, or one a layer group): True, where they
        lie through each slot's table; False, one flat list;
        ``kinds.ROWS``, a row a slot."""
        own = self.kind.OWN_PAGES
        return own[g] if isinstance(own, tuple) else own

    def _ladder(self, g: int):
        """Every bucket a burst's list of group ``g`` can take (a burst
        that reads each slot's own pages: its table span; one that
        gathers a row a slot: the most pages a slot's row holds)."""
        top, lowest = self._listable_pages(g), self.kind.LOWEST_BUCKET
        if self._own_pages(g) == kinds.ROWS:
            return [self._slot_pages(g)[1]]
        if self._own_pages(g):
            top = self.seq_table.block_tables.shape[1]
        buckets = [min(lowest, top)]
        while buckets[-1] < top:
            buckets.append(min(2 * buckets[-1], top))
        return buckets

    def decode_buckets(self):
        """Every shape a burst's page lists can take for this engine:
        the decode programs a server loads before it is ready. One group:
        every bucket ``_flat_bucket`` can return. Several: tuples of a
        bucket a group, those that can meet. A slot with ``f`` pages of
        old context lists ``f`` of them in a window group too until the
        window is full, then the window's, so the groups' lists grow
        together and part only for long slots: with ``F`` pages listed
        in the full group, a window group lists at least what the
        fewest, longest slots would and at most min(F, all windows).
        Where the full group is read through the slots' own tables its
        bucket is the longest slot's span ``n``: a window group lists at
        least what that slot alone would and at most every slot's
        min(n, window)."""
        if len(self.windows) == 1:
            return self._ladder(0)
        top = self.seq_table.block_tables.shape[1]
        B = self.seq_table.block_tables.shape[0]
        shapes, lowest = [], 1
        for bucket in self._ladder(0):
            choices = []
            for g in range(1, len(self.windows)):
                least, most = self._slot_pages(g)
                if self._own_pages(g) == kinds.ROWS:
                    choices.append(self._ladder(g))
                    continue
                if self._own_pages():
                    fewest, listed = min(lowest, least), B * min(bucket, most)
                else:
                    fewest = (lowest // top) * least + min(lowest % top,
                                                           least)
                    listed = min(bucket, B * most)
                choices.append([b for b in self._ladder(g) if
                                self._flat_bucket(fewest, g) <= b
                                <= self._flat_bucket(listed, g)])
            shapes.extend((bucket, *rest)
                          for rest in itertools.product(*choices))
            lowest = bucket + 1
        return shapes

    def _no_pages(self, g: int, table, bucket: int):
        """Group ``g``'s list of a burst in which no slot decodes."""
        B = self.ecfg.max_num_seqs
        if self._own_pages(g) == kinds.ROWS:
            return tuple(map(jnp.asarray, burst_rows(
                table.block_tables, self.ecfg.page_size, bucket, ())))
        if self._own_pages(g):
            return jnp.zeros((B, bucket), jnp.int32)
        return jnp.asarray(burst_gather(
            table.block_tables, self.ecfg.page_size, bucket, ()))

    def load_decode_programs(self) -> int:
        """Run ``decode_burst`` once in every shape a plain greedy round
        can take, with no slot active: every row is dropped and no page
        changes, and the program is compiled (or read from the compile
        cache) before the first request needs it. A width is an operand,
        so the shapes are ``decode_buckets()``; a round with a sampled
        request compiles its sampler on first use, as before. For a
        server to call before it reports ready, not for ``__init__``: a
        bare engine (the tests build dozens) compiles what it meets.
        One span, ``rt.engine.load``; its seconds and the programs are
        added to the counters ``load_s`` and ``loaded_programs``, which
        only this and ``load_prefill_programs`` move. Returns the number
        of programs."""
        B = self.ecfg.max_num_seqs
        zi, zf = jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)
        lora = None
        if self.lora_pool is not None:
            lora = self.lora_pool.select([0] * B)
        buckets = self.decode_buckets()
        with tracing.span("rt.engine.load") as sp:
            for shape in buckets:
                lists = tuple(
                    self._no_pages(g, t, bucket)
                    for g, (t, bucket) in enumerate(zip(self.seq_tables, (
                        shape if isinstance(shape, tuple) else (shape,)))))
                self._run(
                    decode_burst, zi, zi,
                    self._tables(), jnp.zeros(B, bool), self.cos, self.sin,
                    0, zf, zi, zf, lora,
                    lists if len(lists) > 1 else lists[0], jnp.int32(1),
                    cfg=self.cfg, n_steps=self.ecfg.decode_burst,
                    greedy=True)
            jax.block_until_ready(self.cache.k)
        self._counters["loaded_programs"] += len(buckets)
        self._counters["load_s"] += sp.seconds
        return len(buckets)

    # the rungs of a whole prompt's rows (``runner.prefill_bucket``)
    _PREFILL_RUNGS = PREFILL_RUNGS

    def _prefill_rows(self, prompt_len: int) -> int:
        """The rows a whole prompt of ``prompt_len`` tokens runs."""
        return prefill_bucket(prompt_len, self.ecfg.max_seq_len,
                              rungs=self._PREFILL_RUNGS)

    def load_prefill_programs(self) -> int:
        """Run ``prefill_sample`` once at every rung this engine pads a
        whole prompt to (``_PREFILL_RUNGS`` below ``max_seq_len``), with
        a prompt of no tokens, through the call ``_run_prefill`` makes
        (``_dispatch_prefill``), so the entry the jit cache then holds is
        the one a greedy request finds. Every row is dropped: no page
        changes, slot 0's state is put back as it was, no counter of
        served work moves and the sampler's seed stays. For a server to
        call before it reports ready, as ``load_decode_programs``: a lone
        request of a warm-up ladder meets every power of two (and
        ``max_seq_len``, where that caps the bucket) on first use, as
        before, but a rung only by chance, and a first use inside a
        measured window stalls the engine's thread for the whole load. An
        engine that prefills in chunks, or has no rung, loads nothing.
        The span and the counters are ``load_decode_programs``'. Returns
        the number of programs."""
        if self.ecfg.prefill_chunk > 0:
            return 0
        buckets = [r for r in self._PREFILL_RUNGS
                   if r < self.ecfg.max_seq_len]
        if not buckets:
            return 0
        lora = None
        if self.lora_pool is not None:
            lora = self.lora_pool.select([0])
        state = None if self.cache.s is None else self.cache.s[:, :1]
        with tracing.span("rt.engine.load") as sp:
            for bucket in buckets:
                toks, _counts = self._dispatch_prefill(
                    np.zeros((1, bucket), np.int32), 0, 0,
                    self._sampling_arrays([None], advance=0), lora)
            jax.block_until_ready(toks)
        if state is not None:
            self.cache.s = jax.lax.dynamic_update_slice_in_dim(
                self.cache.s, state, 0, 1)
        self._counters["loaded_programs"] += len(buckets)
        self._counters["load_s"] += sp.seconds
        return len(buckets)

    def _sampling_arrays(self, row_states, advance: int = 1):
        n = len(row_states)
        temp = np.ones(n, np.float32)
        top_k = np.zeros(n, np.int32)
        top_p = np.ones(n, np.float32)
        # all-greedy rounds compile the argmax-only epilogue (runner
        # prefill_sample/decode_burst `greedy`): identical outputs,
        # simpler program (inactive slots count as greedy)
        greedy = True
        for i, s in enumerate(row_states):
            if s is None:
                continue
            temp[i] = s.params.temperature
            top_k[i] = s.params.top_k
            top_p[i] = s.params.top_p
            if s.params.temperature > 0.0:
                greedy = False
        seed = self._seed
        self._seed += advance  # burst step i uses seed+i: no reuse
        return (seed, jnp.asarray(temp), jnp.asarray(top_k),
                jnp.asarray(top_p), greedy)

    def _run_prefill(self, state: RequestState) -> List[StepOutput]:
        """Prefill the sequence so far (prompt, plus prior output when
        resuming after preemption — vLLM's recompute-preemption) and
        sample the next token. Whole-prompt mode fuses everything in one
        dispatch; chunked mode advances ONE chunk and only samples after
        the final chunk."""
        if not state.prefill_start_t:
            state.prefill_start_t = time.perf_counter()
        seq = state.prompt + state.output
        L = len(seq)
        C = self.ecfg.prefill_chunk
        if C > 0:
            return self._run_prefill_chunk(state, seq, L, C)
        with self._phase("prefill.build"):
            bucket = self._prefill_rows(L)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :L] = seq
            sampling = self._sampling_arrays([state])
            lora = None
            if self.lora_pool is not None:
                lora = self.lora_pool.select(
                    [self.lora_pool.slot_of(state.model_id)])
        with self._phase("prefill.dispatch"):
            toks, counts = self._dispatch_prefill(tokens, L, state.slot,
                                                  sampling, lora)
        self._count(0, L)
        state.ctx_len = L
        self._counters["prefills"] += 1
        self._counters["prefill_tokens"] += L
        self._counters["prefill_bucket_tokens"] += bucket
        with self._phase("prefill.sync"):
            tok = int(self._read_back(toks, counts)[0])
        if not state.output:
            state.first_token_t = time.perf_counter()
        with self._phase("append"):
            return [self._append_token(state, tok)]

    def _dispatch_prefill(self, tokens, prompt_len: int, slot: int,
                          sampling, lora):
        """THE call of ``prefill_sample``: a request's (``_run_prefill``)
        and the loader's (``load_prefill_programs``), so that both key one
        entry of the jit cache (the same operands, types, weak types and
        static arguments). tokens: numpy int32 [1, bucket]; sampling:
        what ``_sampling_arrays`` returns. Returns (tokens, counts)."""
        seed, temp, top_k, top_p, greedy = sampling
        return self._run(
            prefill_sample,
            jnp.asarray(tokens), jnp.asarray([prompt_len], jnp.int32),
            self._rows(slot),
            self.cos, self.sin, seed, temp, top_k, top_p, lora,
            **self._slot_of(slot), cfg=self.cfg, greedy=greedy)

    def _slot_of(self, slot: int) -> Dict[str, Any]:
        """The keyword by which a prefill learns whose state it writes;
        nothing for a configuration without state layers."""
        if self.cache.s is None:
            return {}
        return {"slots": jnp.asarray([slot], jnp.int32)}

    def compile_prefill(self, prompt_len: int):
        """``(bucket, compiled)``: the whole-prompt prefill program a
        greedy prompt of ``prompt_len`` tokens runs, compiled from
        abstract arguments (read back from the compile cache where it
        has run before). For reading what was really built —
        ``compiled.as_text()``, ``memory_analysis()`` — not for
        running."""
        def abstract(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        def row(dtype):
            return jax.ShapeDtypeStruct((1,), dtype)

        bucket = self._prefill_rows(prompt_len)
        params, ck, cv, ci, cc, cs, cos, sin = jax.tree.map(
            abstract, (self.params, self.cache.k, self.cache.v,
                       self.cache.i, self.cache.c, self.cache.s, self.cos,
                       self.sin))
        return bucket, prefill_sample.lower(
            params, ck, cv, jax.ShapeDtypeStruct((1, bucket), jnp.int32),
            row(jnp.int32), jax.tree.map(abstract, self._rows(0)),
            cos, sin, 0, row(jnp.float32), row(jnp.int32),
            row(jnp.float32), None, ci, cc, cs,
            None if cs is None else row(jnp.int32), cfg=self.cfg,
            greedy=True).compile()

    def _run_prefill_chunk(self, state: RequestState, seq: List[int],
                           L: int, C: int) -> List[StepOutput]:
        from .runner import prefill_chunk, sample_logits

        with self._phase("prefill.build"):
            start = state.prefill_pos
            n = min(C, L - start)
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :n] = seq[start:start + n]
            # table span bucketed over the pages this chunk can touch, so
            # a handful of executables serve every prompt length
            span = self._span_bucket(
                -(-(start + n) // self.ecfg.page_size))
        with self._phase("prefill.dispatch"):
            bt = tuple(jnp.asarray(t.block_tables[
                state.slot:state.slot + 1, :span]) for t in self.seq_tables)
            bt = bt if len(bt) > 1 else bt[0]
            logits, counts = self._run(
                prefill_chunk,
                jnp.asarray(tokens), jnp.int32(start), jnp.int32(n), bt,
                self.cos, self.sin, **self._slot_of(state.slot),
                cfg=self.cfg)
        self._count(start, start + n)
        if counts is not None:
            self._pending_counts.append(counts)
        state.prefill_pos = start + n
        self._counters["prefills"] += 1
        self._counters["prefill_tokens"] += n
        self._counters["prefill_bucket_tokens"] += C
        if state.prefill_pos < L:
            return []  # more chunks to go; decode interleaves meanwhile
        with self._phase("prefill.build"):
            if self.prefix_cache is not None and state.prompt_page_keys:
                # prompt pages are now fully written: publish them for
                # future requests sharing the prefix
                table = self.seq_table.block_tables[state.slot]
                self.prefix_cache.insert(
                    state.prompt_page_keys,
                    [int(p) for p in table[:len(state.prompt_page_keys)]])
            seed, temp, top_k, top_p, _greedy = self._sampling_arrays(
                [state])
        with self._phase("prefill.dispatch"):
            toks = sample_logits(logits, seed, temp, top_k, top_p)
        with self._phase("prefill.sync"):
            tok = int(self._read_back(toks)[0])
        state.ctx_len = L
        if not state.output:
            state.first_token_t = time.perf_counter()
        with self._phase("release"):
            self._release(state)     # the chunks held the whole prompt
        with self._phase("append"):
            return [self._append_token(state, tok)]

    def _preempt(self, state: RequestState) -> None:
        """Recompute-preemption (vLLM style): release the sequence's
        pages and put it back at the head of the waiting queue; its
        generated-so-far tokens re-prefill on readmission."""
        state.preemptions += 1
        self._counters["preemptions"] += 1
        if self.spec is not None:
            self.spec.drop(state.slot)   # drafter KV dies with the pages
        self._free_slot_pages(state.slot)
        self.slots[state.slot] = None
        state.slot = -1
        state.ctx_len = 0
        state.prefill_pos = 0  # chunked progress restarts with the pages
        state.cached_tokens = 0
        try:
            self._prefill_queue.remove(state)
        except ValueError:
            pass
        self.waiting.appendleft(state)

    def _free_slot_pages(self, slot: int) -> None:
        for allocator, table in zip(self.allocators, self.seq_tables):
            allocator.free(table.pages_of(slot))
            table.clear(slot)

    def _pick_victim(self, exclude: RequestState) -> Optional[RequestState]:
        candidates = [s for s in self.slots
                      if s is not None and s is not exclude]
        if not candidates:
            return None
        return max(candidates, key=lambda s: s.arrival_t)  # youngest

    def _burst_width(self) -> int:
        """Fused steps this round: capped by every active slot's headroom
        to max_seq_len and by its remaining token budget (don't burn a
        full burst when everyone needs one more token). Mid-prefill
        slots (ctx_len 0) don't decode and don't cap the burst."""
        K = self.ecfg.decode_burst
        for s in self.slots:
            if s is None or s.ctx_len == 0:
                continue
            K = min(K, self.ecfg.max_seq_len - 1 - s.ctx_len + 1,
                    s.params.max_tokens - len(s.output))
        return max(1, K)

    def _provision_pages(self, s: RequestState, upto: int) -> None:
        """Ensure slot pages cover positions [0, upto) in every group
        (from its first live page on); preempt youngest others when a
        pool runs dry (init guarantees a lone sequence always fits)."""
        for allocator, table in zip(self.allocators, self.seq_tables):
            while s.slot >= 0 and (int(table.n_pages[s.slot])
                                   * self.ecfg.page_size < upto):
                if allocator.free_pages < 1 and self.prefix_cache:
                    self.prefix_cache.evict(1)   # cache before victims
                if allocator.free_pages >= 1:
                    table.append_page(s.slot, allocator.allocate(1)[0])
                    continue
                victim = self._pick_victim(exclude=s)
                if victim is None:
                    raise MemoryError(
                        "single sequence exhausted the KV cache — "
                        "num_pages/max_seq_len misconfigured")
                self._preempt(victim)

    def _run_decode(self) -> List[StepOutput]:
        if self.spec is not None:
            outs = self._run_spec_decode()
            if outs is not None:
                return outs
        B = self.ecfg.max_num_seqs
        counters = self._counters
        with self._phase("decode.build"):
            # the ONE call of _burst_width a round: the benchmark's
            # replica wraps it on the instance to watch the rounds
            K = self._burst_width()
            counters["rounds"] += 1
            counters["decode_steps"] += K
            counters["width_hist"][K] += 1
            for s in [s for s in self.slots
                      if s is not None and s.ctx_len > 0]:
                if s.slot < 0:
                    continue  # preempted as a victim earlier this round
                self._provision_pages(s, s.ctx_len + K)
            # mid-prefill slots (chunked) hold pages but don't decode yet
            active_states = [s for s in self.slots
                             if s is not None and s.ctx_len > 0]
            if not active_states:
                return []
            counters["active_slot_steps"] += K * len(active_states)
            tokens = np.zeros(B, np.int32)
            positions = np.zeros(B, np.int32)
            active = np.zeros(B, bool)
            for s in active_states:
                last = s.output[-1] if s.output else s.prompt[-1]
                tokens[s.slot] = last
                positions[s.slot] = s.ctx_len
                active[s.slot] = True
            seed, temp, top_k, top_p, greedy = self._sampling_arrays(
                self.slots, advance=K)
            lora = None
            if self.lora_pool is not None:
                ids = [0] * self.ecfg.max_num_seqs
                for s2 in active_states:
                    ids[s2.slot] = self.lora_pool.slot_of(s2.model_id)
                lora = self.lora_pool.select(ids)
        with self._phase("release"):
            # a group's list: the pages that hold old context its layers
            # can still see
            page, lists, shape = self.ecfg.page_size, [], []
            for g, window in enumerate(self.windows):
                if self._own_pages(g) is True:
                    # nothing is copied: the burst reads each slot's own
                    # pages through its table, cut to the longest's bucket
                    pages = [-(-s.ctx_len // page) for s in active_states]
                    bucket = self._latent_span(max(pages))
                    lists.append(self._bt(bucket))
                    live = gathered = sum(pages)
                else:
                    held = []
                    for s in active_states:
                        first = 0 if window is None else \
                            self._first_live_page(s.ctx_len, window)
                        held.append((s.slot, -(-s.ctx_len // page) - first,
                                     first))
                    live = sum(n for _slot, n, _first in held)
                    tables = self.seq_tables[g].block_tables
                    if self._own_pages(g) == kinds.ROWS:
                        # a row a slot, every slot's copied
                        bucket, = self._ladder(g)
                        gathered = bucket * tables.shape[0]
                        lists.append(tuple(map(jnp.asarray, burst_rows(
                            tables, page, bucket, held))))
                    else:
                        bucket = gathered = self._flat_bucket(live, g)
                        lists.append(jnp.asarray(burst_gather(
                            tables, page, bucket, held)))
                shape.append(bucket)
                for c in (counters, counters["groups"][self.group_names[g]]):
                    c["live_pages"] += live
                    c["gathered_pages"] += gathered
            hist = counters["gather_hist"]
            shape = shape[0] if len(shape) == 1 else "/".join(
                map(str, shape))
            hist[shape] = hist.get(shape, 0) + 1
        with self._phase("decode.dispatch"):
            toks, counts = self._run(
                decode_burst,
                jnp.asarray(tokens), jnp.asarray(positions), self._tables(),
                jnp.asarray(active), self.cos, self.sin,
                seed, temp, top_k, top_p, lora,
                tuple(lists) if len(lists) > 1 else lists[0],
                jnp.int32(K), cfg=self.cfg,
                n_steps=self.ecfg.decode_burst, greedy=greedy)
        for s in active_states:
            self._count(s.ctx_len, s.ctx_len + K, True)
        with self._phase("decode.sync"):
            sampled = self._read_back(toks, counts)  # [K, B]
        outs = []
        with self._phase("append"):
            for s in active_states:
                for k in range(K):
                    s.ctx_len += 1
                    outs.append(
                        self._append_token(s, int(sampled[k, s.slot])))
                    if s.finished:
                        break
        with self._phase("release"):
            for s in active_states:
                self._release(s)
        return outs

    # --- speculative decoding (spec_decode.py; Leviathan et al.) ---

    def _spec_eligible(self, s: RequestState) -> bool:
        """Greedy-only speculation: accept-prefix semantics reproduce
        the greedy oracle exactly. Sampled/LoRA requests coexist in the
        same verify window (position 0 only) unsped."""
        return s.params.temperature == 0.0 and s.model_id is None

    def _run_spec_decode(self) -> Optional[List[StepOutput]]:
        """One draft+verify round over the whole slot batch: the drafter
        proposes k tokens per eligible slot, verify_step scores every
        slot's window in ONE dispatch (non-drafted slots are a 1-token
        window — they advance one token, like a plain decode step), and
        accept-prefix emits 1..k+1 tokens per drafted slot. Returns None
        when no slot can draft this round (caller falls back to the
        plain decode burst)."""
        from .spec_decode import accept_prefix

        spec = self.spec
        kd = spec.k

        def can_draft(s: RequestState) -> bool:
            # the window [p .. p+k] must fit under max_seq_len, and a
            # request one token from its budget gains nothing
            return (self._spec_eligible(s)
                    and s.ctx_len + kd <= self.ecfg.max_seq_len - 1
                    and s.params.max_tokens - len(s.output) >= 2)

        # no phase is open yet: the plain round that takes over opens
        # its own
        if not any(s is not None and s.ctx_len > 0 and can_draft(s)
                   for s in self.slots):
            return None
        with self._phase("decode.draft"):
            # provision BEFORE array assembly — may preempt victims, so
            # drafted/active sets are derived again afterwards
            for s in [s for s in self.slots
                      if s is not None and s.ctx_len > 0]:
                if s.slot < 0:
                    continue  # preempted as a victim earlier this round
                upto = s.ctx_len + (kd + 1 if can_draft(s) else 1)
                self._provision_pages(s, upto)
            active_states = [s for s in self.slots
                             if s is not None and s.ctx_len > 0]
            if not active_states:
                return []
            drafted_states = [s for s in active_states if can_draft(s)]
            if not drafted_states:
                return None
            # lazy drafter warm-up: first drafted round for a slot (or the
            # first after a drop) prefills the draft KV for its sequence
            for s in drafted_states:
                if s.slot not in spec.ready:
                    seq = s.prompt + s.output
                    spec.prefill(seq[:s.ctx_len],
                                 self.seq_table.block_tables[
                                     s.slot:s.slot + 1])
                    spec.ready.add(s.slot)
            span = self._active_span()
            bt = self._bt(span)
            items = []
            for s in drafted_states:
                seq = s.prompt + s.output
                p = s.ctx_len
                items.append((s.slot, seq[p - 1], seq[p], p))
            drafts = spec.draft(items, bt)
            # fleet mode: ship (KV snapshot, draft) to a prefill-class
            # verifier racing the local verify below; by the greedy-
            # continuation equivalence both compute the same emission, so
            # the remote result is corroboration + placement, never truth
            remote: Dict[int, List[int]] = {}
            if self._spec_remote_verify is not None:
                for s in drafted_states:
                    try:
                        payload = self.snapshot_kv_request(s.request_id)
                        res = self._spec_remote_verify(payload,
                                                       drafts[s.slot])
                    except Exception:
                        res = None
                    if res is not None:
                        remote[s.slot] = [int(t) for t in res]
        with self._phase("decode.build"):
            B = self.ecfg.max_num_seqs
            S = kd + 1
            tok = np.zeros((B, S), np.int32)
            pos = np.full((B, S), -1, np.int32)
            for s in active_states:
                tok[s.slot, 0] = s.output[-1] if s.output else s.prompt[-1]
                pos[s.slot, 0] = s.ctx_len
                d = drafts.get(s.slot)
                if d:
                    tok[s.slot, 1:1 + len(d)] = d
                    pos[s.slot, 1:1 + len(d)] = (
                        s.ctx_len + 1 + np.arange(len(d)))
            seed, temp, top_k, top_p, greedy = self._sampling_arrays(
                self.slots, advance=1)
        with self._phase("decode.dispatch"):
            t0 = time.perf_counter()
            tgt, samp0, counts = self._run(
                verify_step, jnp.asarray(tok),
                jnp.asarray(pos), bt, self.cos, self.sin, seed, temp,
                top_k, top_p, cfg=self.cfg, greedy=greedy)
        with self._phase("decode.sync"):
            tgt = self._read_back(tgt, counts)
            samp0 = np.asarray(samp0)
        spec.verify_times.append(time.perf_counter() - t0)
        outs: List[StepOutput] = []
        with self._phase("append"):
            for s in active_states:
                if s.slot < 0 or s.finished:
                    continue
                d = drafts.get(s.slot)
                if d:
                    emitted = accept_prefix(d, tgt[s.slot].tolist())
                    spec.on_round(len(d), len(emitted) - 1)
                    r = remote.get(s.slot)
                    if r is not None:
                        spec.remote_rounds_total += 1
                        if r == emitted:
                            spec.remote_agree_total += 1
                else:
                    emitted = [int(samp0[s.slot])]
                for t in emitted:
                    s.ctx_len += 1
                    outs.append(self._append_token(s, t))
                    if s.finished:
                        break
        return outs

    def verify_request(self, request_id: str,
                       draft: List[int]) -> List[int]:
        """Run ONE verification round for a single request against an
        externally-supplied draft (the fleet verifier: the draft came
        from a decode-class replica's drafter, the KV arrived via
        inject_request). Applies and returns the emission — identical
        to the monolithic round by accept-prefix semantics. An empty
        draft degenerates to one plain greedy step. Greedy-only; other
        slots in the batch are untouched."""
        from .spec_decode import accept_prefix

        state = self.requests.get(request_id)
        if state is None:
            raise ValueError(f"unknown request {request_id!r}")
        if state.finished or state.slot < 0 or state.ctx_len <= 0:
            raise ValueError(
                f"request {request_id!r} is not verifiable "
                f"(finished={state.finished}, ctx_len={state.ctx_len})")
        if state.params.temperature != 0.0:
            raise ValueError("speculative verification is greedy-only")
        if state.model_id is not None:
            raise ValueError("speculative verification does not "
                             "support LoRA requests")
        draft = [int(t) for t in draft]
        # clamp the window to the sequence budget (mirrors the
        # monolithic round's eligibility rule near max_seq_len)
        while draft and (state.ctx_len + len(draft)
                         > self.ecfg.max_seq_len - 1):
            draft.pop()
        kd = len(draft)
        self._provision_pages(state, state.ctx_len + kd + 1)
        B = self.ecfg.max_num_seqs
        tok = np.zeros((B, kd + 1), np.int32)
        pos = np.full((B, kd + 1), -1, np.int32)
        seq = state.prompt + state.output
        tok[state.slot, 0] = seq[-1]
        pos[state.slot, 0] = state.ctx_len
        if kd:
            tok[state.slot, 1:] = draft
            pos[state.slot, 1:] = state.ctx_len + 1 + np.arange(kd)
        seed, temp, top_k, top_p, _g = self._sampling_arrays(
            self.slots, advance=1)
        span = self._span_bucket(int(self.seq_table.n_pages[state.slot]))
        t0 = time.perf_counter()
        tgt, _s0, counts = self._run(
            verify_step, jnp.asarray(tok),
            jnp.asarray(pos), self._bt(span), self.cos, self.sin,
            seed, temp, top_k, top_p, cfg=self.cfg, greedy=True)
        row = self._read_back(tgt, counts)[state.slot].tolist()
        if self.spec is not None:
            self.spec.verify_times.append(time.perf_counter() - t0)
        emitted = accept_prefix(draft, row)
        if self.spec is not None and kd:
            self.spec.on_round(kd, len(emitted) - 1)
        for t in emitted:
            state.ctx_len += 1
            self._append_token(state, t)
            if state.finished:
                break
        return emitted

    def _append_token(self, state: RequestState, token: int) -> StepOutput:
        state.output.append(token)
        reason = None
        if token in state.params.stop_token_ids:
            reason = "stop"
        elif len(state.output) >= state.params.max_tokens:
            reason = "length"
        elif state.ctx_len + 1 >= self.ecfg.max_seq_len:
            reason = "length"
        if reason:
            self._finish(state, reason)
        return StepOutput(state.request_id, token, state.finished,
                          state.finish_reason,
                          text_offset=len(state.output) - 1)

    def _finish(self, state: RequestState, reason: str) -> None:
        state.finished = True
        state.finish_reason = reason
        if state.slot >= 0:
            if self.spec is not None:
                self.spec.drop(state.slot)
            self._free_slot_pages(state.slot)
            self.slots[state.slot] = None
            state.slot = -1
        elif state in self.waiting:
            self.waiting.remove(state)
        # bounded retention: a long-lived serving engine must not keep
        # every finished request's token lists forever
        self._finished_order.append(state.request_id)
        while len(self._finished_order) > self.ecfg.finished_retention:
            old = self._finished_order.popleft()
            stale = self.requests.get(old)
            if stale is not None and stale.finished:
                del self.requests[old]

    # --- fleet KV plane: prefill->decode handoff ---

    def export_kv_request(self, request_id: str) -> Dict[str, Any]:
        """Export a prefilled request's KV pages for decode on ANOTHER
        engine (disaggregated prefill/decode serving — DistServe/
        Splitwise lineage; llm/serve.py pools). Valid once the request
        has prefilled (ctx_len > 0), typically right after its first
        sampled token. Copies the sequence's pages to host memory,
        finishes the request locally (reason "handoff" — its slot and
        pages free immediately for the next prompt) and returns a
        payload :meth:`inject_request` accepts on the decode engine."""
        self._refuse("kv_transfer", "export_kv_request")
        payload = self.snapshot_kv_request(request_id)
        self._finish(self.requests[request_id], "handoff")
        return payload

    def snapshot_kv_request(self, request_id: str) -> Dict[str, Any]:
        """Non-destructive :meth:`export_kv_request`: same payload, but
        the request keeps running HERE. The fleet spec-verify path ships
        snapshots to a prefill-class verifier while local decode
        continues — both compute the identical emission (spec_decode.py
        module docstring), so nothing is handed off."""
        self._refuse("kv_transfer", "snapshot_kv_request")
        state = self.requests.get(request_id)
        if state is None:
            raise ValueError(f"unknown request {request_id!r}")
        if state.finished or state.slot < 0 or state.ctx_len <= 0:
            raise ValueError(
                f"request {request_id!r} is not exportable "
                f"(finished={state.finished}, ctx_len={state.ctx_len})")
        n_kv = self.allocator.pages_needed(state.ctx_len)
        pages = self.seq_table.pages_of(state.slot)[:n_kv]
        idx = jnp.asarray(pages, jnp.int32)
        return {
            "prompt": list(state.prompt),
            "output": list(state.output),
            "ctx_len": state.ctx_len,
            "page_size": self.ecfg.page_size,
            "model_id": state.model_id,
            "k": np.asarray(self.cache.k[:, idx]),
            "v": np.asarray(self.cache.v[:, idx]),
        }

    def inject_request(self, payload: Dict[str, Any],
                       params: Optional[SamplingParams] = None,
                       request_id: Optional[str] = None) -> str:
        """Admit a request whose prompt pass ran on ANOTHER engine (the
        decode half of disaggregated serving). The shipped pages land in
        free cache pages and the request joins decode directly — no
        prefill compute here. When they CAN'T land (no free slot,
        page-size mismatch, pool pressure, malformed/missing arrays)
        the request joins the waiting queue and recomputes its prefill
        locally (recompute-preemption semantics): slower, never wrong."""
        self._refuse("kv_transfer", "inject_request")
        prompt = [int(t) for t in payload["prompt"]]
        output = [int(t) for t in payload.get("output") or ()]
        ctx_len = int(payload["ctx_len"])
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.ecfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_seq_len "
                f"{self.ecfg.max_seq_len}")
        model_id = payload.get("model_id")
        if model_id is not None:
            if self.lora_pool is None:
                raise ValueError("model_id requires EngineConfig."
                                 "lora_rank > 0")
            self.lora_pool.slot_of(model_id)
        rid = request_id or f"req-{next(self._id)}"
        if rid in self.requests:
            rid = f"req-{next(self._id)}"
        state = RequestState(rid, prompt, params or SamplingParams(),
                             output=output,
                             arrival_t=time.perf_counter(),
                             model_id=model_id)
        self.requests[rid] = state
        if output and len(output) >= state.params.max_tokens:
            # already at its token budget: nothing left to decode
            self._finish(state, "length")
            return rid
        k, v = payload.get("k"), payload.get("v")
        usable = (
            k is not None and v is not None and output
            and int(payload.get("page_size", -1)) == self.ecfg.page_size
            and len(prompt) <= ctx_len < self.ecfg.max_seq_len
            and tuple(k.shape) == (self.cfg.n_layers, k.shape[1],
                                   self.ecfg.page_size,
                                   self.cfg.n_kv_heads,
                                   self.cfg.head_dim)
            and tuple(v.shape) == tuple(k.shape)
            and k.shape[1] >= self.allocator.pages_needed(ctx_len))
        if not usable or not self._inject_pages(state, k, v, ctx_len):
            self.waiting.append(state)  # recompute fallback
        return rid

    def _inject_pages(self, state: RequestState, k, v,
                      ctx_len: int) -> bool:
        slot = self._free_slot()
        if slot < 0:
            return False
        n_kv = self.allocator.pages_needed(ctx_len)
        # headroom for the next decoded token too (mirrors _admit's +1)
        need = self.allocator.pages_needed(ctx_len + 1)
        if not self.allocator.can_allocate(need) and self.prefix_cache:
            self.prefix_cache.evict_for(ctx_len + 1)
        if not self.allocator.can_allocate(need):
            return False
        pages = self.allocator.allocate(need)
        idx = jnp.asarray(pages[:n_kv], jnp.int32)
        self.cache = KVCache(
            self.cache.k.at[:, idx].set(
                jnp.asarray(k[:, :n_kv], self.cache.k.dtype)),
            self.cache.v.at[:, idx].set(
                jnp.asarray(v[:, :n_kv], self.cache.v.dtype)))
        state.slot = slot
        state.ctx_len = ctx_len
        state.prefill_pos = ctx_len
        if not state.first_token_t:
            # prefilled elsewhere: no queue or prefill to wait for here
            state.admit_t = state.prefill_start_t = state.first_token_t = (
                time.perf_counter())
        self.slots[slot] = state
        if self.cache.s is not None:
            # the slot's state of every state layer starts from zero
            # (a resumed request too: it prefills again)
            self.cache.s = zero_slot_state(self.cache.s, jnp.int32(slot))
            self._counters[self.kind.SLOT_RESET] += 1
        self.seq_table.assign(slot, pages)
        if self.prefix_cache is not None:
            # shipped pages double as prefix-cache warmth: register the
            # prompt's full pages so future shared-prefix requests on
            # THIS engine skip their prefill too (same insert the
            # chunked prefill path does after filling them itself)
            keys = PrefixCache.page_keys(state.prompt,
                                         self.ecfg.page_size)
            n_reg = min(len(keys), n_kv)
            if n_reg > 0:
                self.prefix_cache.insert(keys[:n_reg], pages[:n_reg])
                state.prompt_page_keys = keys
        return True

    # --- LoRA management (vLLM add_lora/remove_lora analog) ---

    def add_lora(self, name: str, adapter=None, *, seed: int = 0) -> None:
        """Load an adapter into the pool (``adapter`` defaults to a
        fresh zero-delta init at the engine's rank)."""
        if self.lora_pool is None:
            raise ValueError("engine built without lora_rank")
        if adapter is None:
            from .lora import init_lora_adapter

            adapter = init_lora_adapter(
                jax.random.PRNGKey(seed), self.cfg,
                self.ecfg.lora_rank, dtype=self.cfg.dtype)
        self.lora_pool.add(name, adapter)

    def remove_lora(self, name: str) -> None:
        if self.lora_pool is None:
            raise ValueError("engine built without lora_rank")
        users = [s.request_id for s in self.requests.values()
                 if s.model_id == name and not s.finished]
        if users:
            # removal mid-flight would KeyError inside a later step(),
            # killing the whole batch including base-model requests
            raise RuntimeError(
                f"adapter {name!r} is in use by {len(users)} live "
                f"request(s); drain or abort them first")
        self.lora_pool.remove(name)

    # --- metrics ---

    def stats(self) -> Dict[str, Any]:
        out = {
            "running": sum(s is not None for s in self.slots),
            "waiting": len(self.waiting),
            "free_pages": sum(a.free_pages for a in self.allocators),
            "total_pages": sum(a.num_pages - 1 for a in self.allocators),
        }
        if self.spec is not None:
            out["spec"] = self.spec.stats()
        if self.cache.s is not None:
            # what a cached position holds in the block layers' pools,
            # and what a slot holds in the linear layers' state pool
            out["kv_bytes_per_token"] = self._counters["kv_bytes_per_token"]
            out["state_bytes_per_slot"] = self.cfg.state_bytes_per_slot
        # the grouped expert kernel's tiles for this configuration's
        # product shapes, chosen where its programs were traced; none
        # without experts
        expert_tiles = (chosen_tiles(self.cfg.dim, self.cfg.mlp_dim)
                        if self.cfg.n_experts else {})
        out["counters"] = {**self._counters,
                           "expert_tiles": expert_tiles,
                           "width_hist": list(self._counters["width_hist"]),
                           "host_s": dict(self._counters["host_s"]),
                           "gather_hist": dict(
                               self._counters["gather_hist"]),
                           "groups": {
                               name: {**group, "free_pages": a.free_pages,
                                      "total_pages": a.num_pages - 1}
                               for (name, group), a in zip(
                                   self._counters["groups"].items(),
                                   self.allocators)}}
        return out
