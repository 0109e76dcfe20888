"""Kernels: how close the scan layers' chunked prefill comes to the
chip's memory bandwidth. The bytes the kernel has to move for the prompts
prefilled in the traced stretch (the family's ``scan_prefill_bytes``: a
token and channel, ``u`` and ``dt`` read and ``y`` written in float32, in
every scan layer; each prompt's bytes times the share of ITS prefill that
lay inside the stretch, as ``sparse_prefill_roofline`` counts) over the
published HBM bandwidth are the least time the scan can take; its share
of the device seconds of the kernel's own events there (``device_ops``
under the name the program gives its ``pallas_call``:
``rt_scan_prefill``). The recurrence is 16 state indices a channel and
token with an ``exp`` each, all on the vector unit, which bounds the
kernel, not HBM: the share reads LOW, never high. None where the trace
holds no such event (a program without the kernel) or the family states
no count."""

import importlib.util
import os

from benchmarks.harness import families, peaks

NAME, UNIT, SOURCE = "scan_prefill_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNELS = ("rt_scan_prefill",)


def _prefills(engine: dict) -> list:
    """``sparse_prefill_roofline._prefills``: that file is an accepted
    reader and is loaded by its path, as ``run.py`` loads it."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "sparse_prefill_roofline.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics.sparse_prefill_roofline", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module._prefills(engine)


def compute(run):
    try:
        if (run.get("device") or {}).get("platform") != "tpu":
            return None     # a share of a TPU's peak exists only on a TPU
        bytes_of = getattr(families.family_of(run["config"]),
                           "scan_prefill_bytes", None)
        trace = run.get("trace") or {}
        seconds = sum(s for kind, s in trace.get("device_ops") or ()
                      if kind in KERNELS)
        if bytes_of is None or not seconds or "t0" not in trace:
            return None
        needed = 0.0
        for begun, end, prompt_tokens in _prefills(run["engine"]):
            inside = min(end, trace["t1"]) - max(begun, trace["t0"])
            if inside > 0 and end > begun:
                needed += (bytes_of(run["config"], prompt_tokens)
                           * inside / (end - begun))
        if not needed:
            return None
        return 100.0 * needed / seconds / peaks.peaks_of(
            run["device"]["kind"])["hbm_bytes_per_s"]
    except Exception:
        return None
