"""What every cell needs of the runtime: start it, hold it to the
machine's chips, and read a worker's log when the chip's holder fails."""

from __future__ import annotations

import ctypes
import glob
import os
import signal
import sys
import time


class CellError(Exception):
    """The cell cannot run here, or ran and broke: no result is printed
    and the exit code is not 0."""


def adopt_descendants() -> None:
    """From here on a process that loses its parent becomes this
    process's child, not init's. The runtime's factory forks its workers
    into sessions of their own and exits before they do, so nothing else
    that this process can see holds them all. ``end_descendants`` is its
    other half."""
    pr_set_child_subreaper = 36
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(pr_set_child_subreaper, 1, 0, 0, 0) != 0:
        raise CellError("prctl(PR_SET_CHILD_SUBREAPER) failed: "
                        + os.strerror(ctypes.get_errno()))


def _processes() -> dict:
    """pid -> (state, parent, command) of every process there is."""
    found = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat", "rb") as f:
                state, ppid = f.read().rsplit(b")", 1)[1].split()[:2]
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                command = f.read().replace(b"\0", b" ").decode(
                    errors="replace").strip()
        except (OSError, IndexError, ValueError):
            continue                    # gone between listing and reading
        found[int(entry)] = (state.decode(), int(ppid), command)
    return found


def _descendants() -> dict:
    """The processes below this one, as ``_processes`` gives them."""
    found, me = _processes(), os.getpid()
    below = {}
    for pid in found:
        p = pid
        while p in found and p != me:
            p = found[p][1]
        if p == me and pid != me:
            below[pid] = found[pid]
    return below


def end_descendants(timeout_s: float = 120.0) -> list:
    """Kill and reap every process below this one, and return only when
    none is left: a run leaves nothing behind, on any path out of it.
    Runs after ``ray_tpu.shutdown()``, which has asked them all to go;
    whatever is still alive then is killed and named in the return value
    (a zombie that only wanted reaping is not). A chip's holder that has
    exited stays a zombie that cannot be reaped while the kernel takes
    its device mappings down: 4 s with one chip, 16 s with four (PR 23)."""
    t0 = time.time()
    killed = {}
    while True:
        below = _descendants()
        for pid, (state, _, command) in below.items():
            if state not in ("Z", "X"):
                killed.setdefault(pid, command[:120])
                try:
                    os.kill(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        try:
            while os.waitpid(-1, os.WNOHANG)[0]:
                pass
        except ChildProcessError:
            pass
        if not below:
            return sorted(killed.values())
        if time.time() - t0 > timeout_s:
            raise CellError(f"processes still here {timeout_s}s after "
                            f"SIGKILL: {below}")
        time.sleep(0.02)


def start_runtime(chips: int, rehearsal: bool) -> float:
    """``ray_tpu.init()`` with the native core (built into the checkout
    on the first run, found there by later ones). Returns the chips the
    node reports; fewer than the cell asks for is an error unless the
    configuration is the CPU rehearsal."""
    import ray_tpu
    from ray_tpu import _native

    if _native.get_lib() is None:
        raise CellError("native core unavailable: "
                        f"{_native.native_unavailable_reason()}")
    adopt_descendants()
    ray_tpu.init()
    detected = ray_tpu.cluster_resources().get("TPU", 0.0)
    if detected < chips and not rehearsal:
        raise CellError(f"this cell needs {chips} TPU chip(s); "
                        f"ray_tpu.init() reports TPU={detected}")
    return detected


def stop_runtime() -> dict:
    """``ray_tpu.shutdown()``, then nothing of it is left. Returns, for
    the run's notes, how long each took and what ``end_descendants``
    had to kill."""
    import ray_tpu

    t0 = t1 = time.time()
    try:
        ray_tpu.shutdown()
    finally:
        t1 = time.time()
        killed = end_descendants()
    return {"shutdown_s": t1 - t0, "sweep_s": time.time() - t1,
            "killed": killed}


def check_device(device: dict, chips: int, rehearsal: bool) -> None:
    """The chip's holder is another process than the driver and jax there
    reports the cell's chips, on a TPU (anything goes in the rehearsal,
    which says what it ran on)."""
    if device["pid"] == os.getpid():
        raise CellError("the driver holds the device")
    if rehearsal:
        return
    if device["platform"] != "tpu" or device["count"] != chips:
        raise CellError(f"want {chips} TPU device(s), the holder reports "
                        f"{device['count']} x {device['platform']}")


def wait_gone(pids, timeout_s: float = 120.0) -> float:
    """The chip is free again only once its holder's process is gone."""
    from ray_tpu._private.device_plane import process_alive

    t0 = time.time()
    while any(process_alive(p) for p in pids):
        if time.time() - t0 > timeout_s:
            raise CellError(f"processes {pids} still alive {timeout_s}s "
                            f"after shutdown")
        time.sleep(0.05)
    return time.time() - t0


def dump_worker_logs(tail_bytes: int = 4000) -> None:
    """The end of every worker log of this session that recorded an
    error, to stderr: the cause is rarely in the driver."""
    import ray_tpu
    from ray_tpu._private.config import session_log_dir

    node = ray_tpu._worker_api.node()
    if node is None:
        return
    for path in sorted(glob.glob(os.path.join(
            session_log_dir(node.session_name), "worker-*.log"))):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - tail_bytes))
            tail = f.read().decode(errors="replace")
        if "Traceback" in tail or "Error" in tail:
            print(f"--- {path}\n{tail}", file=sys.stderr, flush=True)
