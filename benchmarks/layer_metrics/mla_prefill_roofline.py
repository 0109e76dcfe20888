"""Kernels: the share of the chip's bf16 peak that the flash kernel
reaches on latent attention's expanded heads in whole-prompt prefill.
The operations the attention of the traced prefills needs (the family's
``latent_attention_flops`` of each request whose first token fell in the
traced stretch, averaged, times the runs of ``prefill_sample`` there: 2
operations x (192 for a score + 128 for a value) x 128 heads x the
causal pairs, in every layer) over the device seconds of the kernel's
own events (``device_ops`` under the name the program gives its
``pallas_call``: ``flash_mla_fwd``) and the published peak. A bucket's
padding and the blocks on the diagonal, computed whole, are work the
kernel does and the count leaves out, so the share reads low for them,
never high. None where the trace holds no such event or the family
states no count."""

from benchmarks.harness import families, peaks

NAME, UNIT, SOURCE = "mla_prefill_roofline", "%", "device_trace"
LAYER, MOVES, KINDS = "Kernels", "ttft_p95_ms", ("serve",)
KERNEL = "flash_mla_fwd"


def compute(run):
    if run["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    flops_of = getattr(families.family_of(run["config"]),
                       "latent_attention_flops", None)
    trace = run.get("trace") or {}
    seconds = sum(s for kind, s in trace.get("device_ops") or ()
                  if kind == KERNEL)
    runs = sum(p["runs"] for name, p in (trace.get("programs") or {}).items()
               if "prefill_sample" in name)
    prompts = [r["prompt_tokens"] for r in run["engine"]["finished"]
               if r["first"] is not None
               and trace.get("t0", 0) <= r["first"] <= trace.get("t1", -1)]
    if flops_of is None or not seconds or not runs or not prompts:
        return None
    needed = (sum(flops_of(run["config"], n) for n in prompts)
              / len(prompts) * runs)
    return 100.0 * needed / seconds / peaks.peaks_of(
        run["device"]["kind"])["bf16_flops"]
