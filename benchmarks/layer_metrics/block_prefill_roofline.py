"""Kernels: the share of the chip's bf16 peak that the sparse layers'
block-sparse flash forward reaches in whole-prompt prefill. The
operations the CHOSEN blocks' attention needs for the prompts prefilled
in the traced stretch (the family's ``block_attention_flops``: for every
query from ``dense_len`` on, a head's score and value of every token of
its 64 chosen blocks, in every sparse layer; by the share of each
prefill inside the stretch, as ``linear_prefill_roofline`` counts) over
the device seconds of the kernel's own events there
(``flash_block_sparse_fwd``) and the published peak. The kernel walks
key blocks of 512 and computes a whole one wherever any of its query
block's 128 queries chose anything in it (on seeded weights the queries
of a block choose differently, so nearly every visible key block is
computed), which the count leaves out: the share reads LOW, never high.
None where the trace holds no such event (no prompt of the stretch
passed ``dense_len``, or a program without the kernel)."""

import importlib.util
import os

NAME, UNIT, SOURCE = "block_prefill_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNELS = ("flash_block_sparse_fwd",)


def compute(run):
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "linear_prefill_roofline.py")
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics.linear_prefill_roofline", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.share_of_peak(run, KERNELS, "block_attention_flops")
    except Exception:
        return None
