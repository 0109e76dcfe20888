"""LLM replica and engine: what giving pages back costs the device. The
seconds of the listed idle gaps (``run["trace"]["idle_gaps"]``) that carry
the name ``rt.engine.release`` (the engine's phase that returns a window
group's pages behind the window after a burst, and builds each layer
group's list of live pages before the next) over the decode rounds of the
traced stretch, in milliseconds a round. A program whose cache has one
layer group still has the phase (it builds the one list there). 0 where
no listed gap carries the name: the phase was never what the device
waited for longest. None without a trace or without decode rounds."""

NAME, UNIT, SOURCE = "kv_release_gap_ms", "ms", "device_trace"
LAYER, MOVES, KINDS = "LLM replica and engine", "tpot_p95_ms", ("serve",)


def compute(run):
    trace = run.get("trace") or {}
    gaps = trace.get("idle_gaps")
    rounds = [r for r in (run.get("engine") or {}).get("rounds", ())
              if trace.get("t0", 0) <= r["t"] <= trace.get("t1", -1)]
    if not gaps or not rounds:
        return None
    return 1e3 * sum(seconds for name, seconds in gaps
                     if name == "rt.engine.release") / len(rounds)
