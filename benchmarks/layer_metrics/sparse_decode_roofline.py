"""Kernels: how close a decode step's scoring and choosing come to the
chip's memory bandwidth. To score a step's queries the indexer's key of
every live position has to be read once (the family's
``indexer_decode_bytes``: 64 values a position a layer, averaged over the
steps of the rounds started in the traced stretch); over the published
HBM bandwidth that is the least time the scoring and the choice of a step
can take. Its share of the device seconds a step spends in the two
kernels' own events (``device_ops`` under the names the program gives
its ``pallas_call``s inside ``decode_burst``: ``rt_sparse_index_decode``
the scores, ``rt_sparse_select_decode`` the choice, over the steps the
traced decode programs ran, as ``mla_decode_roofline`` reads
``rt_mla_decode``). The kernels read rows stored in slots of 128 lanes,
tables padded to a bucket's span and, to choose, a row of float32 scores
32 times over in VMEM, all of which the count leaves out, so the share
reads LOW for them, never high. The fetch of the chosen K and V rows and
the product over them are XLA fusions with no name ``harness/trace.py``
keeps: NO metric reads them, and this one does not guess at them
(PERF.md section 7). None where the trace holds no such kernel event or
the family states no count."""

from benchmarks.harness import families, peaks, readers

NAME, UNIT, SOURCE = "sparse_decode_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "tpot_p95_ms", ("serve",)
KERNELS = ("rt_sparse_index_decode", "rt_sparse_select_decode")


def compute(run):
    if (run.get("device") or {}).get("platform") != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    try:
        bytes_of = getattr(families.family_of(run["config"]),
                           "indexer_decode_bytes", None)
        decode = readers.decode_in_trace(run)
    except Exception:
        return None
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
    # each step of a round scores that round's live positions
    needed = sum(r["width"] * bytes_of(run["config"], r["live"])
                 for r in rounds) / steps
    measured = seconds / (decode["runs"] * decode["mean_width"])
    return 100.0 * needed / peaks.peaks_of(
        run["device"]["kind"])["hbm_bytes_per_s"] / measured
