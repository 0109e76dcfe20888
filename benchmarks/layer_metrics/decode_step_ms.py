"""Model step: device time of one decode step of the whole batch. The
``decode_burst`` programs' device time in the traced stretch, over the
decode steps they ran: the trace gives each program's runs but not its
width, so steps = runs x the mean width the engine chose for the rounds
it started inside the traced stretch."""

from benchmarks.harness import readers

NAME, UNIT, SOURCE = "decode_step_ms", "ms", "device_trace"
LAYER, MOVES, KINDS = "Model step", "tpot_p95_ms", ("serve",)


def compute(run):
    decode = readers.decode_in_trace(run)
    return 1e3 * decode["step_s"] if decode else None
