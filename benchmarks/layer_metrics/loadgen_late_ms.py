"""The benchmark's own generator: how late it sent requests, 95th
percentile of (sent - due). A starved generator must not read as a fast
server; TTFT counts from *due*, so lateness is inside it."""

from benchmarks.harness import stats

NAME, UNIT, SOURCE = "loadgen_late_ms", "ms", "host_clock"
LAYER, MOVES, KINDS = "Benchmark generator", "ttft_p95_ms", ("serve",)


def compute(run):
    late = [r["sent"] - r["due"] for r in run["client"]
            if r["counted"] and r["sent"] is not None]
    return 1e3 * stats.percentile(late, 0.95) if late else None
