"""Device: share of the traced stretch in which no operation ran on the
device (1 - the union of its operations' intervals over the stretch),
averaged over the devices used. train cells."""

from benchmarks.harness import readers

NAME, UNIT, SOURCE = "device_idle.train", "%", "device_trace"
LAYER, MOVES, KINDS = "Device", "train_tok_s", ("train",)


def compute(run):
    return readers.idle_share(run)
