"""LLM replica and engine: device-idle host time per dispatch. The
seconds of the listed idle gaps (``run["trace"]["idle_gaps"]``) that
carry the name of a phase of the serving round (``rt.engine.*``: schedule,
dispatch, sync, append; ``rt.pump.fanout``: the hop through the pump)
over the runs of the ``decode_burst`` and ``prefill_sample`` programs in
the traced stretch, in milliseconds: what the host costs the device each
time it hands it a program. ``rt.pump.idle`` is left out: a device that
waits because no request is there pays no host cost. A program without
spans reads 0; the gaps its spans do not win are ``idle_attributed.serve``'s
to report."""

NAME, UNIT, SOURCE = "engine_host_gap_ms", "ms", "device_trace"
LAYER, MOVES, KINDS = "LLM replica and engine", "tpot_p95_ms", ("serve",)


def compute(run):
    trace = run.get("trace") or {}
    gaps = trace.get("idle_gaps")
    dispatches = sum(
        program["runs"] for name, program in
        (trace.get("programs") or {}).items()
        if "decode_burst" in name or "prefill_sample" in name)
    if not gaps or not dispatches:
        return None
    host = sum(seconds for name, seconds in gaps
               if name.startswith("rt.engine.") or name == "rt.pump.fanout")
    return 1e3 * host / dispatches
