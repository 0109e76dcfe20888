"""LLM replica and engine: median time from ``add_request`` to the first
token inside the engine (queueing for a slot, prefill behind decode
rounds, the prefill itself), for the window's requests."""

from benchmarks.harness import stats

NAME, UNIT, SOURCE = "engine_ttft_ms", "ms", "program_span"
LAYER, MOVES, KINDS = "LLM replica and engine", "ttft_p95_ms", ("serve",)


def compute(run):
    counted = {r.get("rid") for r in run["client"] if r["counted"]}
    waits = [e["first"] - e["arrival"] for e in run["engine"]["finished"]
             if e["rid"] in counted and e["first"] is not None]
    return 1e3 * stats.median(waits) if waits else None
