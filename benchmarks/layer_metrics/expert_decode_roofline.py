"""Kernels: how close a decode step of a family with routed experts
comes to the chip's memory bandwidth. The bytes a step of ``n`` active
rows must read (the family's ``routed_decode_step_bytes``: attention and
the head once, the experts ``n`` rows choose on average and not all of
them, the live keys and values), averaged over the steps of the rounds
started in the traced stretch, over the published HBM bandwidth is the
least time a step can take; its share of the measured step time
(``readers.decode_in_trace``). None for a family without that count:
``decode_burst_roofline`` is theirs."""

from benchmarks.harness import families, peaks, readers

NAME, UNIT, SOURCE = "expert_decode_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "tpot_p95_ms", ("serve",)


def compute(run):
    if run["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    bytes_of = getattr(families.family_of(run["config"]),
                       "routed_decode_step_bytes", None)
    decode = readers.decode_in_trace(run)
    if bytes_of is None or not decode:
        return None
    trace = run["trace"]
    rounds = [r for r in run["engine"]["rounds"]
              if trace["t0"] <= r["t"] <= trace["t1"] and r["active"]]
    steps = sum(r["width"] for r in rounds)
    if not steps:
        return None
    weight_bytes = 1 if run["config"].get("quantize") == "int8" else 2
    # each step of a round reads for that round's rows and positions
    needed = sum(r["width"] * bytes_of(run["config"], r["active"],
                                       r["live"], weight_bytes)
                 for r in rounds) / steps
    least_s = needed / peaks.peaks_of(
        run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / decode["step_s"]
