"""Kernels: how close the decode attention over the latent cache comes
to the chip. A step's attention over the cached rows, absorbed, must
read every live position's row once and score it by 128 heads (the
family's ``latent_decode_cost``: bytes and operations of the rounds'
live positions); at 242 operations a byte it is bound by the HBM
bandwidth and the bf16 peak at once, so the least time a step's
attention can take is the LARGER of bytes over bandwidth and operations
over peak. Its share of the device seconds a step spends in the decode
kernel's own events (``device_ops`` under the name the program gives its
``pallas_call``: ``rt_mla_decode``, over the steps the traced decode
programs ran). The kernel reads whole blocks of 512 positions and rows
padded to whole lanes, which the count leaves out, so the share reads
low for them, never high. None where the trace holds no such event (a
program without the kernel, a kernel too short to be among the ten kinds
the reduction lists) or the family states no count."""

from benchmarks.harness import families, peaks, readers

NAME, UNIT, SOURCE = "mla_decode_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "tpot_p95_ms", ("serve",)
KERNEL = "rt_mla_decode"


def compute(run):
    if run["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    cost_of = getattr(families.family_of(run["config"]),
                      "latent_decode_cost", None)
    decode = readers.decode_in_trace(run)
    trace = run.get("trace") or {}
    seconds = sum(s for kind, s in trace.get("device_ops") or ()
                  if kind == KERNEL)
    if cost_of is None or not decode or not seconds:
        return None
    rounds = [r for r in run["engine"]["rounds"]
              if trace["t0"] <= r["t"] <= trace["t1"] and r["active"]]
    steps = sum(r["width"] for r in rounds)
    if not steps:
        return None
    chip = peaks.peaks_of(run["device"]["kind"])

    def least_s(live):
        moved, operations = cost_of(run["config"], live)
        return max(moved / chip["hbm_bytes_per_s"],
                   operations / chip["bf16_flops"])

    # each step of a round attends over that round's live positions
    needed = sum(r["width"] * least_s(r["live"]) for r in rounds) / steps
    measured = seconds / (decode["runs"] * decode["mean_width"])
    return 100.0 * needed / measured
