"""Kernels: how close a decode step's scan-state update comes to the
chip's memory bandwidth. A step of the scan layers has to read every live
slot's float32 state once and write it once (the family's
``scan_decode_bytes`` of the round's decoding slots: 2 x 16 x 5,120 x 4
bytes a slot and layer, averaged over the steps of the rounds started in
the traced stretch); over the published HBM bandwidth that is the least
time the update can take. Its share of the device seconds a step spends
in the kernel's own events (``device_ops`` under the name the program
gives its ``pallas_call`` inside ``decode_burst``: ``rt_scan_decode``,
over the steps the traced decode programs ran, as
``linear_decode_roofline`` reads its kernel). None where the trace holds
no such kernel event or the family states no count."""

from benchmarks.harness import families, peaks, readers

NAME, UNIT, SOURCE = "scan_decode_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "tpot_p95_ms", ("serve",)
KERNELS = ("rt_scan_decode",)


def compute(run):
    try:
        if (run.get("device") or {}).get("platform") != "tpu":
            return None     # a share of a TPU's peak exists only on a TPU
        bytes_of = getattr(families.family_of(run["config"]),
                           "scan_decode_bytes", None)
        decode = readers.decode_in_trace(run)
        trace = run.get("trace") or {}
        seconds = sum(s for kind, s in trace.get("device_ops") or ()
                      if kind in KERNELS)
        if bytes_of is None or not decode or not seconds:
            return None
        rounds = [r for r in run["engine"]["rounds"]
                  if trace["t0"] <= r["t"] <= trace["t1"] and r["active"]]
        steps = sum(r["width"] for r in rounds)
        if not steps:
            return None
        # each step of a round updates that round's decoding slots
        needed = sum(r["width"] * bytes_of(run["config"], r["active"])
                     for r in rounds) / steps
        measured = seconds / (decode["runs"] * decode["mean_width"])
        return 100.0 * needed / peaks.peaks_of(
            run["device"]["kind"])["hbm_bytes_per_s"] / measured
    except Exception:
        return None
