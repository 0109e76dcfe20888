"""Speculative decoding plane (Leviathan et al., ICML'23 — PAPERS.md):
a small drafter proposes k tokens per slot, the target model scores the
whole window in ONE batched forward (runner.verify_step), and
accept-prefix semantics emit the longest agreeing prefix plus one
corrected token — output token-for-token identical to the greedy
oracle, 1..k+1 tokens per round instead of 1.

Key invariant the engine relies on: the emitted tokens are exactly the
first m+1 tokens of the target's greedy continuation, where m is the
length of the longest draft prefix that agrees with it. ANY correct
computation of the greedy continuation therefore yields the identical
emission — which is why the fleet verifier (a prefill-class replica fed
a KV snapshot) and the corrupt-payload recompute fallback can never
diverge from the monolithic round.

Drafter cache discipline: the drafter keeps its OWN paged KV cache but
mirrors the target's block tables (same page ids, no second allocator —
both caches are [layers, num_pages, ...]); shared prefix pages hold
token-identical content in both, so prefix-cache page sharing stays
sound. Each draft round opens with a 2-token repair window [p-1, p]:
after a full accept + bonus, the previous round's last draft token
never ran through the drafter, so the drafter KV can trail the target
by AT MOST one position — which the repair window always rewrites.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..models.llama import LLAMA_CONFIGS, LlamaConfig
from ..ops import rope_frequencies
from .cache import KVCache, init_kv_cache
from .runner import decode_burst, prefill_bucket, verify_step
from .runner import prefill as _runner_prefill

import jax.numpy as jnp


def accept_prefix(draft: Sequence[int], target: Sequence[int]) -> List[int]:
    """Greedy accept-prefix: ``target[j]`` is the target's argmax AFTER
    consuming window position j (the token preceding ``draft[j]``), so
    ``draft[j]`` is accepted iff it equals ``target[j]``. Returns the
    accepted prefix plus ``target[m]`` — the correction on the first
    disagreement, or the free bonus token on a full accept. Always emits
    at least one token; ``len(target)`` must exceed ``len(draft)``."""
    m = 0
    for j, d in enumerate(draft):
        if int(d) != int(target[j]):
            break
        m += 1
    return [int(t) for t in draft[:m]] + [int(target[m])]


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """``speculation={"draft_config": ..., "num_draft_tokens": k}`` as it
    arrives from serve.deployment / YAML. ``draft_config`` names an
    LLAMA_CONFIGS entry (or is a LlamaConfig); ``draft_seed`` seeds the
    drafter's random init when no params are supplied."""
    draft_config: Any
    num_draft_tokens: int = 3
    draft_seed: int = 0

    @classmethod
    def parse(cls, obj: Any) -> "SpecConfig":
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, dict):
            known = {"draft_config", "num_draft_tokens", "draft_seed"}
            extra = sorted(set(obj) - known)
            if extra:
                raise ValueError(f"unknown speculation keys {extra}; "
                                 f"expected a subset of {sorted(known)}")
            if "draft_config" not in obj:
                raise ValueError("speculation requires 'draft_config'")
            return cls(draft_config=obj["draft_config"],
                       num_draft_tokens=int(obj.get("num_draft_tokens", 3)),
                       draft_seed=int(obj.get("draft_seed", 0)))
        raise TypeError(f"speculation must be a dict or SpecConfig, "
                        f"got {type(obj).__name__}")


def _resolve_draft_cfg(dc: Any) -> LlamaConfig:
    if isinstance(dc, LlamaConfig):
        return dc
    if isinstance(dc, str):
        try:
            return LLAMA_CONFIGS[dc]
        except KeyError:
            raise ValueError(
                f"unknown draft_config {dc!r}; known: "
                f"{sorted(LLAMA_CONFIGS)}") from None
    raise TypeError(f"draft_config must be a name or LlamaConfig, "
                    f"got {type(dc).__name__}")


class SpecDecoder:
    """Drafter half of the spec-decode plane: owns the draft model's
    params + paged KV cache and proposes k tokens per drafted slot. The
    engine owns scheduling, verification and emission."""

    def __init__(self, target_cfg: LlamaConfig, ecfg, spec_cfg,
                 draft_params=None):
        sc = SpecConfig.parse(spec_cfg)
        if sc.num_draft_tokens < 1:
            raise ValueError("num_draft_tokens must be >= 1")
        dcfg = _resolve_draft_cfg(sc.draft_config)
        if ecfg.max_seq_len > dcfg.max_seq:
            raise ValueError(
                f"draft model max_seq {dcfg.max_seq} < engine "
                f"max_seq_len {ecfg.max_seq_len}")
        if dcfg.vocab < target_cfg.vocab:
            # still CORRECT (the drafter just can never propose ids >=
            # its vocab, so those positions always reject) but almost
            # certainly a tokenizer mismatch — refuse loudly
            raise ValueError(
                f"draft vocab {dcfg.vocab} < target vocab "
                f"{target_cfg.vocab}: drafter cannot propose every "
                f"target token")
        self.spec_cfg = sc
        self.dcfg = dcfg
        self.ecfg = ecfg
        self.k = sc.num_draft_tokens
        if draft_params is None:
            from ..models.llama import init_params

            draft_params = init_params(
                jax.random.PRNGKey(sc.draft_seed), dcfg)
        self.params = draft_params
        # mirrors the target's page pool 1:1 — block tables are shared
        self.cache = init_kv_cache(dcfg, ecfg.num_pages, ecfg.page_size,
                                   None)
        cos, sin = rope_frequencies(dcfg.head_dim, dcfg.max_seq,
                                    dcfg.rope_theta)
        self.cos, self.sin = jax.device_put(cos), jax.device_put(sin)
        # slots whose draft cache currently covers their sequence; a
        # drop() (preempt/finish/handoff) forces a fresh warm-up prefill
        self.ready: set = set()
        # counters, drained by the serve metrics pump
        self.drafted_total = 0
        self.accepted_total = 0
        self.emitted_total = 0
        self.rounds_total = 0
        self.remote_rounds_total = 0
        self.remote_agree_total = 0
        self.verify_times: List[float] = []

    # --- bookkeeping ---

    def drop(self, slot: int) -> None:
        self.ready.discard(slot)

    def reset(self) -> None:
        self.ready.clear()

    def on_round(self, drafted: int, accepted: int) -> None:
        self.drafted_total += drafted
        self.accepted_total += accepted
        self.emitted_total += accepted + 1
        self.rounds_total += 1

    @property
    def acceptance_ratio(self) -> float:
        return (self.accepted_total / self.drafted_total
                if self.drafted_total else 0.0)

    def take_verify_times(self) -> List[float]:
        out, self.verify_times = self.verify_times, []
        return out

    def stats(self) -> Dict[str, Any]:
        return {
            "draft_tokens": self.drafted_total,
            "accepted_tokens": self.accepted_total,
            "rounds": self.rounds_total,
            "acceptance_ratio": self.acceptance_ratio,
            "remote_rounds": self.remote_rounds_total,
            "remote_agree": self.remote_agree_total,
        }

    # --- device work ---

    def prefill(self, tokens: Sequence[int], block_row) -> None:
        """Warm the drafter KV for positions [0, len(tokens)) of one
        slot (first drafted round, or resume after drop). ``block_row``
        is the slot's [1, max_pages] block-table row."""
        L = len(tokens)
        bucket = prefill_bucket(L, self.ecfg.max_seq_len)
        tok = np.zeros((1, bucket), np.int32)
        tok[0, :L] = tokens
        _logits, ck, cv, _n = _runner_prefill(
            self.params, self.cache.k, self.cache.v, jnp.asarray(tok),
            jnp.asarray([L], jnp.int32), jnp.asarray(block_row),
            self.cos, self.sin, None, cfg=self.dcfg)
        self.cache = KVCache(ck, cv)

    def draft(self, items: Sequence[Tuple[int, int, int, int]],
              bt) -> Dict[int, List[int]]:
        """Propose k tokens per drafted slot. ``items`` rows are
        ``(slot, token_at_p_minus_1, token_at_p, p)`` with p the slot's
        ctx_len; ``bt`` is the device block table [B, span] shared with
        the target. The 2-token repair window [p-1, p] rewrites the at
        most one drafter-KV position the previous round's bonus token
        skipped and yields d_1; a greedy decode burst continues
        d_2..d_k. Returns {slot: [d_1 .. d_k]}."""
        B = int(bt.shape[0])
        tok2 = np.zeros((B, 2), np.int32)
        pos2 = np.full((B, 2), -1, np.int32)
        pos1 = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for slot, t_prev, t_last, p in items:
            tok2[slot] = (t_prev, t_last)
            pos2[slot] = (p - 1, p)
            pos1[slot] = p + 1
            active[slot] = True
        zf = jnp.zeros((B,), jnp.float32)
        zi = jnp.zeros((B,), jnp.int32)
        of = jnp.ones((B,), jnp.float32)
        tgt2, _s0, ck, cv, _n = verify_step(
            self.params, self.cache.k, self.cache.v, jnp.asarray(tok2),
            jnp.asarray(pos2), bt, self.cos, self.sin, 0, zf, zi, of,
            cfg=self.dcfg, greedy=True)
        d1 = tgt2[:, 1]
        if self.k > 1:
            toks, ck, cv, _n = decode_burst(
                self.params, ck, cv, d1, jnp.asarray(pos1), bt,
                jnp.asarray(active), self.cos, self.sin, 0, of, zi, of,
                None, cfg=self.dcfg, n_steps=self.k - 1, greedy=True)
            rest = np.asarray(toks)                        # [k-1, B]
        else:
            rest = np.zeros((0, B), np.int32)
        self.cache = KVCache(ck, cv)
        d1 = np.asarray(d1)
        out: Dict[int, List[int]] = {}
        for slot, _tp, _tl, _p in items:
            out[slot] = [int(d1[slot])] + [int(rest[j, slot])
                                           for j in range(self.k - 1)]
        return out


def remote_verify(engine, payload: Dict[str, Any],
                  draft: Sequence[int],
                  params=None) -> List[int]:
    """Fleet verifier entry point: inject a KV snapshot into ``engine``
    (a scratch verifier on a prefill-class replica), run ONE
    verification round against ``draft`` and return the emission —
    identical to the monolithic round by the greedy-continuation
    equivalence. A corrupt/unusable payload falls back to local
    recompute: the prefill pass itself emits the first greedy token,
    which consumes (or corrects) the first draft token, and the rest of
    the window verifies normally. The scratch request is aborted before
    returning, so repeated calls never accumulate state."""
    from .sampling import SamplingParams

    draft = [int(t) for t in draft]
    pre = [int(t) for t in payload.get("output") or ()]
    if params is None:
        # generous budget: the emission is clipped by the CALLER's real
        # request, never by the scratch verifier
        params = SamplingParams(
            temperature=0.0, max_tokens=len(pre) + len(draft) + 4)
    rid = engine.inject_request(payload, params=params)
    state = engine.requests[rid]
    try:
        emitted: List[int] = []
        if state.ctx_len <= 0 and not state.finished:
            # recompute fallback: drive admission+prefill only; the
            # prefill epilogue samples exactly one greedy token
            guard = 0
            limit = 4 * (len(payload.get("prompt") or ()) + len(pre) + 8)
            while not state.finished and state.ctx_len <= 0:
                engine.step(skip_decode=True)
                guard += 1
                if guard > limit:
                    raise RuntimeError(
                        f"recompute fallback for {rid} made no progress")
            fresh = [int(t) for t in state.output[len(pre):]]
            for t in fresh:
                emitted.append(t)
                if draft and draft[0] == t:
                    draft.pop(0)
                else:
                    return emitted       # correction: round is over
        if state.finished:
            return emitted
        emitted.extend(engine.verify_request(rid, draft))
        return emitted
    finally:
        engine.abort_request(rid)
