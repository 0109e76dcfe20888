"""LLM replica and engine: how much of the device's idle time the
program's own spans explain. Of the idle gaps the trace reduction lists
(the ten names that hold most idle time among the fifty longest gaps,
``run["trace"]["idle_gaps"]``), the seconds whose name starts with ``rt.``
(a ``util/tracing.span`` of the serving round: ``rt.engine.*``,
``rt.pump.*``) over the seconds of all of them. What is left is device
idle time under host code that no span of the program covers: the measure
of what the tracing cannot see. Whether a span wins a gap's name is the
reduction's rule (the host event that overlaps the gap most, among those
at most 20 times its length), so a program without spans reads 0."""

NAME, UNIT, SOURCE = "idle_attributed.serve", "%", "device_trace"
LAYER, MOVES, KINDS = "LLM replica and engine", "serve_tok_s", ("serve",)


def compute(run):
    gaps = (run.get("trace") or {}).get("idle_gaps")
    total = sum(seconds for _name, seconds in gaps or ())
    if not total:
        return None
    ours = sum(seconds for name, seconds in gaps if name.startswith("rt."))
    return 100.0 * ours / total
