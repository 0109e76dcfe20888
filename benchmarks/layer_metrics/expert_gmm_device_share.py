"""Kernels: the share of the device's busy time, in the traced stretch,
spent in the grouped expert products: the seconds of the grouped
kernel's own events (``device_ops`` under the name the program gives its
``pallas_call``: ``rt_moe_gmm``, the gate, up and down products of every
expert layer, in prefill and in a decode step alike) over the device's
busy seconds. On a chip that holds a share of the experts the products
see a share of the rows, and what is left of a step is attention, the
shared expert and the sorting of rows. None where the trace holds no
such event (a program without the kernel, a configuration without
experts, a kernel too short to be listed)."""

NAME, UNIT, SOURCE = "expert_gmm_device_share", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNEL = "rt_moe_gmm"


def compute(run):
    trace = run.get("trace") or {}
    seconds = sum(s for kind, s in trace.get("device_ops") or ()
                  if kind == KERNEL)
    if not seconds or not trace.get("busy_s"):
        return None
    return 100.0 * seconds / trace["busy_s"]
