"""Kernels: the share of the chip's bf16 peak that whole-prompt prefill's
sparse attention reaches. The operations the equations need for the
indexer's scores and the chosen keys (the family's
``sparse_attention_flops`` of a prompt: a score of every visible pair at
16 heads of 64, a head's score and value of every CHOSEN pair, in every
layer) over the device seconds of the three kernels' own events in the
traced stretch (``device_ops`` under the names the program gives its
``pallas_call``s: ``rt_sparse_index`` the scores, ``rt_sparse_select``
the choice, ``flash_sparse_fwd`` the product) and the published peak.

A prefill here lasts 0.6 to 3 s of a 4 s stretch, so the stretch's edges
cut prefills, and the kernels' seconds are those INSIDE the stretch. The
operations are counted the same way: each prompt's operations times the
share of ITS prefill that lay inside the stretch (``_prefills``: the
engine runs one prefill at a time, from the end of the decode round
before it, or the first token before it, or its arrival, whichever is
last, to its own first token, all on the engine's clock), the kernels
taken as spread evenly over a prefill's 16 layers. A prefill wholly
inside counts whole, one wholly outside not at all.

The count is of what the equations need whatever implements them: a
product that computes every visible pair and masks, a bucket's padding
and the counting that finds the 2,048th score are work the kernels do
and the count leaves out, so the share reads LOW for them, never high.
None where the trace holds no such event (a program without the kernels,
a stretch whose prompts all stay under the 2,048 keys) or the family
states no count."""

import statistics

from benchmarks.harness import families, peaks

NAME, UNIT, SOURCE = "sparse_prefill_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNELS = ("rt_sparse_index", "rt_sparse_select", "flash_sparse_fwd")


def _prefills(engine: dict) -> list:
    """``[(begun, first token, prompt tokens)]`` of the finished
    requests, on the engine's clock. ``step`` runs ONE prefill and then
    one decode round, and a prefill ends with its first token: it began
    when the engine came free before that (the last decode round's start
    plus a round's own length, which is the median gap between rounds
    with no first token between them; or the first token before), and
    not before its request arrived."""
    rounds = sorted(r["t"] for r in engine.get("rounds") or ())
    firsts = sorted(r["first"] for r in engine["finished"]
                    if r["first"] is not None)
    alone = [b - a for a, b in zip(rounds, rounds[1:])
             if not any(a < f <= b for f in firsts)]
    a_round = statistics.median(alone) if alone else 0.0
    out = []
    for r in engine["finished"]:
        end = r["first"]
        if end is None:
            continue
        free = [r.get("arrival", float("-inf"))]
        free += [min(t + a_round, end) for t in rounds if t < end][-1:]
        free += [f for f in firsts if f < end][-1:]
        out.append((max(free), end, r["prompt_tokens"]))
    return out


def compute(run):
    if (run.get("device") or {}).get("platform") != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    try:
        flops_of = getattr(families.family_of(run["config"]),
                           "sparse_attention_flops", None)
        prefills = _prefills(run["engine"])
    except Exception:
        return None
    trace = run.get("trace") or {}
    seconds = sum(s for kind, s in trace.get("device_ops") or ()
                  if kind in KERNELS)
    if flops_of is None or not seconds or "t0" not in trace:
        return None
    needed = 0.0
    for begun, end, prompt_tokens in prefills:
        inside = min(end, trace["t1"]) - max(begun, trace["t0"])
        if inside > 0 and end > begun:
            needed += (flops_of(run["config"], prompt_tokens)
                       * inside / (end - begun))
    if not needed:
        return None
    return 100.0 * needed / seconds / peaks.peaks_of(
        run["device"]["kind"])["bf16_flops"]
