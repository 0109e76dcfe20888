"""Kernels: how close a decode step comes to the chip's memory
bandwidth. The bytes a step must read (the weights once, int8, and the
keys and values of every live position: the ``decode_step_bytes`` of the
configuration's family) over the published HBM bandwidth is the least
time a step can take; its share of the measured ``decode_step_ms``.
Decode at these batch sizes is bandwidth-bound: 16 rows per weight
read."""

from benchmarks.harness import families, peaks, readers

NAME, UNIT, SOURCE = "decode_burst_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "tpot_p95_ms", ("serve",)


def compute(run):
    if run["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    decode = readers.decode_in_trace(run)
    if not decode:
        return None
    weight_bytes = 1 if run["config"].get("quantize") == "int8" else 2
    needed = families.family_of(run["config"]).decode_step_bytes(
        run["config"], decode["live_tokens"], weight_bytes)
    least_s = needed / peaks.peaks_of(
        run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / decode["step_s"]
