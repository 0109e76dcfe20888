"""A configuration's family: everything the harness has to know about an
architecture, and nothing about traffic, clocks or traces.

A configuration file names its family (``"family": "<name>"``; absent
means ``llama_dense``), and the harness finds
``benchmarks/families/<name>.py`` by that name, as ``run.py`` finds the
readers by listing ``layer_metrics/``: a later PR adds a family file and
edits none that is there. ``benchmarks/README.md`` ("A family") lists
what such a file defines.

The driver imports a family too (for its limits, its counts and the
class a serve cell deploys) and never imports jax, so importing a family
imports no jax: what needs it is imported inside the functions that the
chip's holder calls.
"""

from __future__ import annotations

import importlib
import os

DEFAULT = "llama_dense"
DIRECTORY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "families")

# Keys of a configuration file that are the harness's own, whatever the
# family: what the file is, where it comes from, and how it is deployed.
# A key ending in ``_why`` is a reason for the reader. Every other key is
# the architecture's, and the family has to read it (``CONFIG_KEYS``).
HARNESS_KEYS = frozenset((
    "name", "family", "source", "published", "rehearsal", "reduced",
    "assumed", "deployment", "kind", "quantize", "engine", "mesh"))


def family_of(config: dict):
    """The module ``benchmarks/families/<family>.py`` of a configuration.
    A family that is not there, or a key of the file that neither the
    harness nor the family reads, is an error: a width that is dropped
    in silence is a different model under the published name."""
    name = config.get("family", DEFAULT)
    if not os.path.isfile(os.path.join(DIRECTORY, name + ".py")):
        raise ValueError(
            f"configuration {config.get('name')!r} names the family "
            f"{name!r}, and there is no benchmarks/families/{name}.py "
            f"(looked in {DIRECTORY})")
    family = importlib.import_module("benchmarks.families." + name)
    unread = sorted(k for k in config if k not in HARNESS_KEYS
                    and k not in family.CONFIG_KEYS
                    and not k.endswith("_why"))
    if unread:
        raise ValueError(
            f"configuration {config.get('name')!r} has keys that the "
            f"family {name!r} does not read: {unread}")
    return family


def chosen_token_margins(forward_logits, params, prompts, answers,
                         config: dict):
    """Teacher-forcing a family's reference on prompt + answer: for every
    token the system chose, how far its reference logit lies below the
    reference's largest logit at that position, in units of that
    position's logit standard deviation. 0 where the reference agrees.
    ``prompts`` [batch, p] and ``answers`` [batch, a] are whole arrays
    (equal lengths within a call). Returns float32 [batch, a]."""
    import jax.numpy as jnp

    tokens = jnp.concatenate([prompts, answers], axis=1)
    logits = forward_logits(params, tokens, config)
    p = prompts.shape[1]
    at = logits[:, p - 1:-1]                      # predicts answers[:, i]
    chosen = jnp.take_along_axis(at, answers[..., None], -1)[..., 0]
    return (at.max(-1) - chosen) / at.std(-1)
