"""Train plane, across chips: share of the traced stretch in which a
device's operation line runs a collective (all-reduce, all-gather,
reduce-scatter, their -start/-done halves) and no compute, averaged over
the devices. Left out where one device ran."""

NAME, UNIT, SOURCE = "collective_exposed_share", "%", "device_trace"
LAYER, MOVES, KINDS = "Train plane", "train_tok_s", ("train",)


def compute(run):
    trace = run.get("trace") or {}
    if trace.get("devices", 0) < 2 or not trace.get("span_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["span_s"]
