"""The seam between the runtime and the device plane.

Two decisions live here and nowhere else:

* **Who gets the TPU backend.** The raylet pins every pool worker to
  ``JAX_PLATFORMS=cpu`` (``pinned_worker_env``); a lease that holds chips
  un-pins the worker that runs it, back to what the node's own
  environment asks of jax (``claim_chips``); a lease that holds none
  keeps the pin (``release_chips``). A worker that claimed chips may hold
  libtpu and the device until it exits, so the raylet retires it with its
  lease (raylet.py ``_retire_chip_worker``) instead of returning it to
  the pool.
* **Where compiled programs are kept** (``enable_compilation_cache``):
  where ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed path inside
  the checkout. The path is part of the cache key, so it never carries a
  pid, a uid or a time.

Importing this module does not import jax.
"""

from __future__ import annotations

import collections
import functools
import os
import sys
from typing import Dict, Sequence

CPU_PIN = "cpu"

# libtpu confines a process to a subset of a host's chips only when the
# per-process bounds describe that subset (ref: accelerators/tpu.py
# TPU_CHIPS_PER_HOST_BOUNDS_{1,2}_CHIP_CONFIG). Both spellings are set:
# TPU VMs export the *_HOST_* names for the whole host and libtpu reads
# either.
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
_BOUNDS_VARS = ("TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_CHIPS_PER_HOST_BOUNDS")
_PROCESS_VARS = ("TPU_PROCESS_BOUNDS", "TPU_HOST_BOUNDS")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# node label: what the node's own environment asks of jax
JAX_PLATFORMS_LABEL = "jax_platforms"

_cache_events: "collections.Counter[str]" = collections.Counter()


def node_jax_platforms() -> str:
    """What this node's environment asks of jax ("" when unset). A node
    publishes it as its ``JAX_PLATFORMS_LABEL`` label and hands it to its
    workers (``pinned_worker_env``)."""
    return os.environ.get("JAX_PLATFORMS", "")


def allows_tpu(jax_platforms: str) -> bool:
    """Whether a process started under this ``JAX_PLATFORMS`` may take a
    TPU: "tpu,cpu" on a TPU host makes jax FAIL at start-up when it
    cannot take the chip; under "cpu" (the test suite) it never takes
    one, whatever it leases; unset leaves the choice to jax."""
    return not jax_platforms or "tpu" in jax_platforms.split(",")


def pinned_worker_env(node_chips: int) -> Dict[str, str]:
    """What the raylet adds to every worker's environment: the CPU pin,
    and what ``claim_chips`` needs to take it off again."""
    return {"JAX_PLATFORMS": CPU_PIN,
            "RAY_TPU_NODE_JAX_PLATFORMS": node_jax_platforms(),
            "RAY_TPU_NODE_CHIPS": str(node_chips)}


def claim_chips(chip_ids: Sequence[int]) -> None:
    """Give this process the node's device plane, confined to
    ``chip_ids`` (the raylet's per-lease chip accounting). Must run
    before jax is imported: a process whose jax already started under
    the CPU pin can never reach the chip, and must not pretend to.
    Holders check the platform they got (LLMServer, chip_smoke.py)."""
    if "RAY_TPU_CHIP_IDS" not in os.environ:      # still pinned
        if "jax" in sys.modules:
            raise RuntimeError(
                f"a lease holding TPU chips {list(chip_ids)} landed in a "
                f"process where jax was already imported under "
                f"JAX_PLATFORMS=cpu; the device plane cannot be reclaimed "
                f"there. Chip leases need a fresh worker.")
        node_platforms = os.environ.get("RAY_TPU_NODE_JAX_PLATFORMS", "")
        if node_platforms:
            os.environ["JAX_PLATFORMS"] = node_platforms
        else:
            os.environ.pop("JAX_PLATFORMS", None)
    ids = ",".join(str(i) for i in chip_ids)
    os.environ["RAY_TPU_CHIP_IDS"] = ids
    node_chips = int(os.environ.get("RAY_TPU_NODE_CHIPS", "0") or 0)
    if len(chip_ids) >= node_chips:
        # the whole host: libtpu's own view of it is already right
        return
    os.environ["TPU_VISIBLE_CHIPS"] = ids
    bounds = _SUBSET_BOUNDS.get(len(chip_ids))
    if bounds is not None:
        for var in _BOUNDS_VARS:
            os.environ[var] = bounds
        for var in _PROCESS_VARS:
            os.environ[var] = "1,1,1"


def release_chips() -> None:
    """A chipless lease: visibility left over from an earlier lease must
    not leak (the chips may be someone else's now)."""
    os.environ.pop("TPU_VISIBLE_CHIPS", None)
    os.environ.pop("RAY_TPU_CHIP_IDS", None)


def process_alive(pid: int) -> bool:
    """False once ``pid`` has exited. A zombie counts as gone: it has
    already closed its files, the device among them (``os.kill(pid, 0)``
    would call it alive until its parent reaps it)."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            # "pid (comm) S ..." — comm may itself hold spaces and parens
            state = f.read().rsplit(b")", 1)[1].split()[0]
    except (OSError, IndexError):
        return False
    return state not in (b"Z", b"X")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    return its directory. ``JAX_COMPILATION_CACHE_DIR`` wins — jax reads
    it itself and this code sets no directory then; otherwise the cache
    sits at ``DEFAULT_CACHE_DIR``. Workers inherit the variable from the
    raylet's environment."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache only compiles that cost real time — sub-second ones would
    # grow the directory without shortening a restart
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _count_cache_events()
    return path


@functools.cache
def _count_cache_events() -> None:
    """Register the hit/miss listener, once per process."""
    import jax

    def on_event(event: str, **_kwargs) -> None:
        if event.startswith("/jax/compilation_cache/"):
            _cache_events[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)


def compilation_cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses seen by this process since
    ``enable_compilation_cache``."""
    return {"hits": _cache_events["cache_hits"],
            "misses": _cache_events["cache_misses"]}
