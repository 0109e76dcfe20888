"""Device: of the time in which a request WAS in the system, the share in
which the device ran nothing: ``(span_s - busy_s - lull_s) / (span_s -
lull_s)``, where ``lull_s`` is the seconds of the listed idle gaps
(``run["trace"]["idle_gaps"]``) named ``rt.pump.lull`` (what
``lull_share.serve`` reads). ``device_idle.serve`` with the waits for
traffic taken out of both sides: what the host's rounds cost the device
under load, the number a change to the engine's round is written against,
and the one that tells an under-loaded cell from a host-bound one. Equal
to ``device_idle.serve`` where no listed gap carries the name (a program
without the span, a stretch without a lull); 0.0 where the lulls take the
whole stretch; None only without a trace, where ``device_idle.serve``
reads None too.

What it cannot see. ``lull_s`` is a lower bound (the listed gaps are the
ten names among the fifty longest gaps), so a lull that is not listed
stays in the numerator as idle under load; and a gap gets ONE name, so
the milliseconds of ``rt.engine.schedule`` and ``rt.engine.prefill.build``
that end a lull are taken out with it."""

NAME, UNIT, SOURCE = "loaded_idle.serve", "%", "device_trace"
LAYER, MOVES, KINDS = "Device", "tpot_p95_ms", ("serve",)


def compute(run):
    trace = run.get("trace") or {}
    if not trace.get("span_s"):
        return None
    lull_s = sum(seconds for name, seconds in trace.get("idle_gaps") or ()
                 if name == "rt.pump.lull")
    loaded_s = trace["span_s"] - lull_s
    if loaded_s <= 0.0:
        return 0.0
    return 100.0 * (loaded_s - trace["busy_s"]) / loaded_s
