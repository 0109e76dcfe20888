"""Cache kinds: what a token (or a slot) leaves behind in a layer, one
module a format, picked from the configuration alone (``of``). The four
programs of ``llm/runner.py`` and the engine ask the kind and know no
format themselves: the next cache shape is one file here, its fields in
``LlamaConfig`` and its operations under ``ops/``.

  paged    K and V rows in pages, one layer group or several, with or
           without a window (Mistral, OLMoE, SmallThinker)
  latent   ONE compressed row a token and no V (DeepSeek-V2)
  indexed  K, V and an indexer's key; a choice of keys (Keye-VL-2.0)
  state    a state a slot beside block-chosen pages (MiniCPM-SALA)
  scan     a selective scan's state and tail a slot beside a window
           group and ONE full layer's pages that eight layers read
           (Phi-4-mini-flash-reasoning)

What a kind gives, in the order a new one is written; plain Python called
while a program is traced, no jit boundary of its own:
  init_pools(cfg, num_pages, page_size, dtype, slots) -> KVCache
  heads(h, lp, lr, state, *, cfg, kind, cos, sin, positions, attend,
        lora_scale) -> (o, kept): a layer's attention half on the
      normalised input: its projections (``runner._heads`` where they
      are q, k and v), the program's ``attend``, whose arguments are the
      kind's own affair, and what follows it before ``wo``; ``kind`` is
      the layer's kind string.
  prefill(cfg, cache, block_tables, prompt_lens, slots, pos_grid, valid)
      -> (attend, write): over the prompt's own rows; ``write(what left
      the layer scan)`` -> the KVCache behind it.
  prefill_chunk(cfg, cache, block_tables, start_pos, chunk_len, slots,
                pos_grid, valid) and
  verify_step(cfg, cache, block_tables, positions, qpos, valid)
      -> (pools, attend, done): ``pools`` ride the layer scan, a tuple a
      layer group; ``done(pools out of it)`` -> the KVCache. A kind that
      has no such program raises by name.
  decode_burst(cfg, cache, block_tables, gather, positions, active, K)
      -> ``Burst``.
  its writer: ``paged._write_rows`` (THE scatter), ``latent.
      _write_slices`` and ``_write_latent_pages`` (loops of slices), or
      one of its own.
  the host's facts: ``OWN_PAGES`` (True: a burst reads each slot's own
      pages through its table and copies none; False: ONE flat list of
      the live pages, copied once a burst; a tuple where the layer
      groups differ, one a group, and there a window group's may be
      ``ROWS``: a row a slot of the pages inside its window, copied once
      a burst, a slot scoring its own row alone), ``LOWEST_BUCKET`` (of a
      burst's
      lists or table spans, in pages), ``SLOT_RESET`` (a kind with a
      state a slot: the counter an admission's zeroing moves),
      ``COUNTERS`` and ``count(cfg,
      counters, page_size, start, end, decode)`` (how the queries at
      positions [start, end) of one sequence move them),
      ``attention_paths(cfg, prefill, on_tpu)``, and ``refuses(cfg)`` ->
      (the kind's name, {feature: why not}) over ``enable_prefix_caching``,
      ``lora_rank``, ``speculation``, ``kv_transfer``: ONE table, read by
      ``LLMEngine._refuse``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple


ROWS = "rows"


class Burst(NamedTuple):
    """A kind's half of ``runner.decode_burst``."""
    old: tuple       # a layer's state before the burst's rows, a group
    scratch: tuple   # the burst's own rows [L, B, K, ...], a group
    carry: Any       # what else the kind keeps in the step loop's carry
    # step(i, new_mask [1, K], carry) -> (attend, carried): step i's
    # attention (``new_mask``: the burst's own rows up to it), and
    # ``carried()`` the carry behind the step's layers
    step: Callable
    # write(scratch, carry, p_grid [B, K], written [B, K]) -> KVCache
    write: Callable


from . import indexed, latent, paged, scan, state  # noqa: E402  (they name Burst)


def of(cfg):
    """The configuration's kind: a pure function of ``LlamaConfig``."""
    if cfg.own_weights:
        return state
    if cfg.scan_state:
        return scan
    if cfg.latent:
        return latent
    return indexed if cfg.sparse_top_k else paged
