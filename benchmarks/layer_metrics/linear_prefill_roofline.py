"""Kernels: the share of the chip's bf16 peak that the lightning layers'
chunked prefill reaches. The operations the RECURRENCE needs for the
prompts prefilled in the traced stretch (the family's
``linear_prefill_flops``: a token and head, ``k^T v`` into the state and
``q S`` out of it, in every lightning layer; each prompt's operations
times the share of ITS prefill that lay inside the stretch, as
``sparse_prefill_roofline`` counts) over the device seconds of the
kernel's own events there (``device_ops`` under the name the program
gives its ``pallas_call``: ``rt_linear_prefill``) and the published
peak. The chunked form multiplies about twice what the recurrence needs
(the decayed ``q k^T`` and its product with ``v`` within a chunk) and
keeps its state and sums in float32, so the share reads LOW, never high.
None where the trace holds no such event (a program without the kernel)
or the family states no count."""

import importlib.util
import os

from benchmarks.harness import families, peaks

NAME, UNIT, SOURCE = "linear_prefill_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNELS = ("rt_linear_prefill",)
FLOPS_OF = "linear_prefill_flops"


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


def share_of_peak(run, kernels, flops_name):
    """Percent of the bf16 peak: the family's ``flops_name`` of every
    prompt, by the share of its prefill inside the traced stretch, over
    the ``kernels``' device seconds there. None where nothing is read."""
    if (run.get("device") or {}).get("platform") != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    try:
        flops_of = getattr(families.family_of(run["config"]), flops_name,
                           None)
        prefills = _prefills(run["engine"])
    except Exception:
        return None
    trace = run.get("trace") or {}
    seconds = sum(s for kind, s in trace.get("device_ops") or ()
                  if kind in kernels)
    if flops_of is None or not seconds or "t0" not in trace:
        return None
    needed = 0.0
    for begun, end, prompt_tokens in prefills:
        inside = min(end, trace["t1"]) - max(begun, trace["t0"])
        if inside > 0 and end > begun:
            needed += (flops_of(run["config"], prompt_tokens)
                       * inside / (end - begun))
    if not needed:
        return None
    return 100.0 * needed / seconds / peaks.peaks_of(
        run["device"]["kind"])["bf16_flops"]


def compute(run):
    try:
        return share_of_peak(run, KERNELS, FLOPS_OF)
    except Exception:
        return None
