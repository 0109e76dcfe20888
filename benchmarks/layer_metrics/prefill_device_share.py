"""Model step: share of the device's busy time, in the traced stretch,
spent in ``prefill_sample`` programs. While a prompt is prefilled every
running stream waits."""

from benchmarks.harness import readers

NAME, UNIT, SOURCE = "prefill_device_share", "%", "device_trace"
LAYER, MOVES, KINDS = "Model step", "ttft_p95_ms", ("serve",)


def compute(run):
    trace = run.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    prefill = readers.program_seconds(trace, "prefill")
    return 100.0 * prefill / trace["busy_s"] if prefill is not None else None
