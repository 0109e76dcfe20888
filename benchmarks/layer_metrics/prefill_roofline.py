"""Kernels: the share of the chip's bf16 peak that whole-prompt prefill
reaches. The operations the prefills of the traced stretch need (the
family's ``prefill_flops`` of each request whose first token fell in the
stretch, averaged, times the runs of ``prefill_sample`` there) over
those programs' device seconds and the published peak. For a family
with routed experts the largest part is the grouped expert product.
A bucket's padding and the sorting of rows are work the program does
and the count leaves out, so the share reads low for them, never high.
None for a family that states no ``prefill_flops``."""

from benchmarks.harness import families, peaks

NAME, UNIT, SOURCE = "prefill_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)


def compute(run):
    if run["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    flops_of = getattr(families.family_of(run["config"]), "prefill_flops",
                       None)
    trace = run.get("trace") or {}
    programs = [p for name, p in (trace.get("programs") or {}).items()
                if "prefill_sample" in name]
    prompts = [r["prompt_tokens"] for r in run["engine"]["finished"]
               if r["first"] is not None
               and trace.get("t0", 0) <= r["first"] <= trace.get("t1", -1)]
    seconds = sum(p["seconds"] for p in programs)
    if flops_of is None or not prompts or not seconds:
        return None
    needed = (sum(flops_of(run["config"], n) for n in prompts)
              / len(prompts) * sum(p["runs"] for p in programs))
    return 100.0 * needed / seconds / peaks.peaks_of(
        run["device"]["kind"])["bf16_flops"]
