"""Kernels: the share of the device's busy time, in the traced stretch,
spent choosing keys: the seconds of the selection kernels' own events
(``device_ops`` under the names the program gives its ``pallas_call``s:
``rt_sparse_select`` in prefill, ``rt_sparse_select_decode`` in a decode
step) over the device's busy seconds. The choice is no matrix product:
it counts, 32 times over a row of scores, how many lie above a value.
Where it sets the pace the indexer has cost more than it saved. None
where the trace holds no such event."""

NAME, UNIT, SOURCE = "select_device_share", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNELS = ("rt_sparse_select", "rt_sparse_select_decode")


def compute(run):
    trace = run.get("trace") or {}
    seconds = sum(s for kind, s in trace.get("device_ops") or ()
                  if kind in KERNELS)
    if not seconds or not trace.get("busy_s"):
        return None
    return 100.0 * seconds / trace["busy_s"]
