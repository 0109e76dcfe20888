"""A train cell, driver side: ``Trainer.fit`` with one gang worker that
holds the cell's chips. The driver never imports jax."""

from __future__ import annotations

import math
import os
import shutil
import time

from . import families
from .runtime import CellError, check_device, wait_gone

# The system computes the probe loss in bf16 (8 bits of mantissa) with
# float32 accumulation, the reference in float32 throughout. At random
# initial weights the loss is ln(vocab) + about 0.5; bf16 rounding of
# logits moves the mean over a thousand tokens by a few parts in ten
# thousand (PR 21 read 2e-4 between two bf16 layouts of one model). A
# dropped layer, an unscaled score or a wrong shift moves it by percents.
# On the chip the two differed by 3e-5 and 4e-5 of the loss (PR 23).
# A family whose block this argument does not cover states its own
# ``LOSS_TOLERANCE``, with its reason; this one is for those that state
# none.
LOSS_TOLERANCE = 5e-4


def run(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
        trace: bool, scratch: str, t_process: float, detected_tpus: float
        ) -> dict:
    from ray_tpu.train import RunConfig, ScalingConfig, Trainer

    from .train_fn import train_fn

    chips = int(cell["chips"])
    rehearsal = bool(config.get("rehearsal"))
    on_tpu = detected_tpus >= chips
    loop_config = {"model": config, "mix": mix, "seed": seed % 2147483647,
                   "seconds": seconds,
                   "trace_dir": os.path.join(scratch, "trace") if trace
                   else None}
    storage = os.path.join(scratch, "train_runs")
    scaling = ScalingConfig(
        num_workers=1, use_tpu=on_tpu,
        resources_per_worker={"CPU": 1, "TPU": chips} if on_tpu else None)
    try:
        result = Trainer(
            train_fn, train_loop_config=loop_config, scaling_config=scaling,
            run_config=RunConfig(name=cell["name"], storage_path=storage)
        ).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise CellError(f"Trainer.fit failed: {result.error}")
    m = result.metrics
    if not m.get("final"):
        raise CellError(f"no final report from the gang worker: {m}")
    check_device(m["device"], chips, rehearsal)
    gone_s = wait_gone([m["device"]["pid"]])

    steps = m["steps"]
    probe = m["probe"]
    tolerance = getattr(families.family_of(config), "LOSS_TOLERANCE",
                        LOSS_TOLERANCE)
    loss_ok = abs(probe["loss"] - probe["reference"]) <= (
        tolerance * abs(probe["reference"]))
    finite = all(math.isfinite(s["loss"]) for s in steps) and all(
        math.isfinite(x) for x in m["warm"]["losses"])
    device = dict(m["device"], memory_peak_bytes=m["memory_peak_bytes"])
    return {
        "kind": "train", "window_s": seconds, "chips": chips,
        "setup_s": m["window_opened_unix"] - t_process,
        "steps": steps, "tokens_per_step": m["tokens_per_step"],
        "trace": m.get("trace") or {}, "device": device,
        "window_compiles": m["window_compiles"],
        "attempted": len(steps), "failed": sum(
            not math.isfinite(s["loss"]) for s in steps),
        "correct": bool(loss_ok and finite),
        "notes": {"probe": probe, "loss_tolerance": tolerance,
                  "warm": m["warm"], "worker_exit_s": gone_s,
                  # seconds after the process started at which the gang
                  # worker reached each phase of set-up
                  "phases_s": {k: t - t_process
                               for k, t in m["phases"].items()},
                  "fit_returned_s": time.time() - t_process,
                  "first_loss": steps[0]["loss"] if steps else None,
                  "last_loss": steps[-1]["loss"] if steps else None},
    }


def end_to_end(run: dict) -> dict:
    """The train cells' end-to-end metrics. The window runs from its
    opening to the end of the step in flight when ``--seconds`` have
    passed, so every step it counts ends inside it and no part of a step
    is cut off; the rate is over all of that time."""
    steps = run["steps"]
    tokens = len(steps) * run["tokens_per_step"]
    length = steps[-1]["ended"] if steps else float("nan")
    return {
        "train_tok_s": (tokens / length / run["chips"], "tokens/s/chip"),
        "setup_s": (run["setup_s"], "s"),
    }
