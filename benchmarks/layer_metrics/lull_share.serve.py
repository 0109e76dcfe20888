"""LLM replica and engine: the share of the traced stretch in which the
device waited because no request was in the system. The seconds of the
listed idle gaps (``run["trace"]["idle_gaps"]``) that carry the name
``rt.pump.lull`` (the replica's pump waiting for a request, in pieces of
at most 50 ms: ``ray_tpu/llm/serve.py``) over ``span_s``. It moves with
the cell's rate and its arrivals, not with the program: a cell that reads
high here is under-loaded, and its ``device_idle.serve`` says little
about the host. 0.0 where no listed gap carries the name (a program
without the span, a stretch without a lull); None only without a trace,
where ``device_idle.serve`` reads None too.

What it cannot see. A gap gets ONE name, that of the host event that
overlaps it most, so the few milliseconds of ``rt.engine.schedule`` and
``rt.engine.prefill.build`` at a lull's end, and of ``rt.pump.fanout``
at its start, are counted as lull. The listed gaps are the ten names
among the fifty longest gaps, so the seconds are a lower bound: a lull
shorter than the fiftieth gap, or the eleventh name, is not in them."""

NAME, UNIT, SOURCE = "lull_share.serve", "%", "device_trace"
LAYER, MOVES, KINDS = "LLM replica and engine", "serve_tok_s", ("serve",)


def compute(run):
    trace = run.get("trace") or {}
    if not trace.get("span_s"):
        return None
    lull_s = sum(seconds for name, seconds in trace.get("idle_gaps") or ()
                 if name == "rt.pump.lull")
    return 100.0 * lull_s / trace["span_s"]
