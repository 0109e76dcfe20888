"""What several per-layer readers share."""

from __future__ import annotations

from typing import Optional


def untraced_step_seconds(run: dict) -> list:
    """Host seconds of each train step not taken under the profiler."""
    return [s["ended"] - s["begun"] for s in run["steps"]
            if not s["traced"]]


def idle_share(run: dict) -> Optional[float]:
    """Percent of the traced stretch in which no operation ran on the
    device, averaged over the devices used."""
    trace = run.get("trace") or {}
    if not trace.get("span_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["span_s"])


def program_seconds(trace: dict, part: str) -> Optional[float]:
    """Device seconds of the programs whose name holds ``part``."""
    found = [p["seconds"] for name, p in (trace.get("programs") or {}).items()
             if part in name]
    return sum(found) if found else None


def decode_in_trace(run: dict) -> Optional[dict]:
    """The decode programs of the traced stretch: device seconds per
    decode step, and the mean number of live cached positions a step
    read. None where the trace or the engine's rounds are missing."""
    trace = run.get("trace") or {}
    programs = {name: p for name, p in (trace.get("programs") or {}).items()
                if "decode_burst" in name}
    rounds = [r for r in run["engine"]["rounds"]
              if trace.get("t0", 0) <= r["t"] <= trace.get("t1", -1)]
    runs = sum(p["runs"] for p in programs.values())
    if not programs or not rounds or not runs:
        return None
    seconds = sum(p["seconds"] for p in programs.values())
    steps_in_rounds = sum(r["width"] for r in rounds)
    mean_width = steps_in_rounds / len(rounds)
    return {
        "step_s": seconds / (runs * mean_width),
        "runs": runs, "rounds": len(rounds), "mean_width": mean_width,
        # each step of a round reads that round's live positions
        "live_tokens": sum(r["live"] * r["width"] for r in rounds)
        / steps_in_rounds,
    }
