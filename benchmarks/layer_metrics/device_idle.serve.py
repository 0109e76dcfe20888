"""Device: share of the traced stretch in which no operation ran on the
device (1 - the union of its operations' intervals over the stretch),
averaged over the devices used. serve cells."""

from benchmarks.harness import readers

NAME, UNIT, SOURCE = "device_idle.serve", "%", "device_trace"
LAYER, MOVES, KINDS = "Device", "serve_tok_s", ("serve",)


def compute(run):
    return readers.idle_share(run)
