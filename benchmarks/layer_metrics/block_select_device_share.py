"""Kernels: the share of the device's busy time, in the traced stretch,
spent scoring and choosing blocks: the seconds of the kernels' own
events (``device_ops`` under the names the program gives its
``pallas_call``s: ``rt_block_score``, the softmax of every query head
over the compressed keys, in prefill and in a decode step alike;
``rt_sparse_select``, the count that finds a prefill query's 64 blocks)
over the device's busy seconds. A decode step's choice is a
``lax.top_k`` over at most 512 scores a slot, an XLA fusion with no
name ``harness/trace.py`` keeps: this reads it as 0. Where the share
sets the pace the selection has cost more than it saved. None where the
trace holds no such event."""

NAME, UNIT, SOURCE = "block_select_device_share", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNELS = ("rt_block_score", "rt_sparse_select")


def compute(run):
    try:
        trace = run.get("trace") or {}
        seconds = sum(s for kind, s in trace.get("device_ops") or ()
                      if kind in KERNELS)
        if not seconds or not trace.get("busy_s"):
            return None
        return 100.0 * seconds / trace["busy_s"]
    except Exception:
        return None
