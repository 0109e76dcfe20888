"""Serve front end: what proxy, handle, replica queue and the streamed
way back add to a request's first token. Per request, the client's time
from *sent* to first streamed token minus the engine's own time from
``add_request`` to first token (``RequestState.first_token_t -
arrival_t``), joined by X-Request-ID; the median over the window's
requests."""

from benchmarks.harness import stats

NAME, UNIT, SOURCE = "frontend_ms", "ms", "host_clock"
LAYER, MOVES, KINDS = "Serve front end", "ttft_p95_ms", ("serve",)


def compute(run):
    engine = {r["rid"]: r for r in run["engine"]["finished"]}
    extra = []
    for r in run["client"]:
        e = engine.get(r.get("rid"))
        if r["counted"] and r["ok"] and e and e["first"] is not None:
            extra.append((r["first"] - r["sent"])
                         - (e["first"] - e["arrival"]))
    return 1e3 * stats.median(extra) if extra else None
