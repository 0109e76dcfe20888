"""Train plane: median host time of one step as a user's loop sees it:
batch made, placed, the instrumented step, the loss read as a number.
Steps taken under the profiler are left out."""

from benchmarks.harness import readers, stats

NAME, UNIT, SOURCE = "step_ms", "ms", "host_clock"
LAYER, MOVES, KINDS = "Train plane", "train_tok_s", ("train",)


def compute(run):
    seconds = readers.untraced_step_seconds(run)
    return 1e3 * stats.median(seconds) if seconds else None
