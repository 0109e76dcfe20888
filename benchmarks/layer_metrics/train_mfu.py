"""Train plane: model FLOP/s utilization of the whole step, on the
host's clock, so a stall of the host lowers it like a slow kernel (with
``device_idle.train`` beside it to tell the two apart). The operations
the forward and backward passes require per token (the
``train_flops_per_token`` of the configuration's family; for a dense
decoder 6 x the parameters a token is multiplied with, without the
embedding table, plus causal attention; recomputation not counted) x
tokens per second per chip, over the chip's published bf16 peak. An
end-to-end utilization, not a kernel's roofline share: the kernels have
no metric of their own in the train cells yet (that needs names for them
in the trace; PERF.md, Open questions)."""

from benchmarks.harness import families, peaks, readers

NAME, UNIT, SOURCE = "train_mfu", "%", "host_clock"
LAYER, MOVES, KINDS = "Train plane", "train_tok_s", ("train",)


def compute(run):
    if run["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    seconds = readers.untraced_step_seconds(run)
    if not seconds:
        return None
    tok_s_chip = (run["tokens_per_step"] * len(seconds) / sum(seconds)
                  / run["chips"])
    flops = families.family_of(run["config"]).train_flops_per_token(
        run["config"], int(run["mix"]["seq"]))
    return 100.0 * flops * tok_s_chip / peaks.peaks_of(
        run["device"]["kind"])["bf16_flops"]
