"""From a profiler trace to numbers: the reduction every PR shares.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
``jax.profiler.ProfileData``, nothing else) into a plain dict of events;
``reduce_events`` turns that dict into device busy time, time by program
and by operation, exposed collective time and the longest idle gaps.
The split is there so that the arithmetic can be checked on a small
recorded dict (``benchmarks/tests/recorded_trace.json``) without jax.

On a TPU a device is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per executed operation (a ``while`` or ``call`` holds its
body's operations nested inside it), whose line ``XLA Modules`` holds one
event per executed program (``jit_decode_burst(...)``) and whose line
``Async XLA Ops`` holds asynchronous operations from start to done (the
lines seen on the chip in PR 23: ``Scalar Unit``, ``XLA Modules``,
``XLA Ops``, ``Async XLA Ops``, ``TC Overlay``). On the CPU
(the rehearsal) the backend's operations are events on the
``tf_XLAPjRtCpuClient`` threads of the host plane; they stand in for a
device so that the rehearsal exercises this code, and mean nothing.
All times are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|\bsend\b|\brecv\b", re.I)
_PROGRAM = re.compile(r"^([A-Za-z_][\w.\-]*)")
_TOP = 10
# kinds a reader looks for by name, kept whatever their rank: the names
# the program gives its own kernels (``flash_*``, ``rt_*``) and scopes
_NAMED = ("flash_", "rt_", "rt.")
_MAX_HOST_EVENTS = 200_000


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """``{"devices": [{"name", "ops": [[name, start, dur]], "modules":
    [...], "async": [...]}], "host": [[name, start, dur]], "lines": {plane: [line names]},
    "window": [first start, last end of any event]}``"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, lines = [], [], {}
    window = [float("inf"), float("-inf")]

    def events(line) -> list:
        out = [[e.name, float(e.start_ns), float(e.duration_ns)]
               for e in line.events]
        if out:
            window[0] = min(window[0], min(e[1] for e in out))
            window[1] = max(window[1], max(e[1] + e[2] for e in out))
        return out

    for plane in data.planes:
        lines[plane.name] = [line.name for line in plane.lines]
        if _TPU_PLANE.match(plane.name):
            device = {"name": plane.name, "ops": [], "modules": [],
                      "async": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device["ops"] = events(line)
                elif line.name == "XLA Modules":
                    device["modules"] = events(line)
                elif line.name == "Async XLA Ops":
                    device["async"] = events(line)
            devices.append(device)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    continue
                if len(host) < _MAX_HOST_EVENTS:
                    host.extend(e for e in events(line) if e[2] > 0)
    if not devices:   # the CPU rehearsal: backend threads stand in
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            ops = [e for line in plane.lines
                   if line.name.startswith("tf_XLAPjRtCpuClient")
                   for e in events(line) if e[2] > 0]
            devices.append({"name": "/host:CPU (stand-in)", "ops": ops,
                            "modules": []})
    return {"devices": devices, "host": host, "lines": lines,
            "window": window}


# ---------------------------------------------------------------- intervals
def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _length(intervals: List[Tuple[float, float]]) -> float:
    return sum(end - start for start, end in intervals)


def _subtract(a: List[Tuple[float, float]],
              b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out, j = [], 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def _leaves(ops: list) -> list:
    """Operations that hold no other operation inside them (a ``while``
    holds its body): the ones that do the work."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    leaves, stack = [], []      # stack of [event, has_child]
    for event in ordered:
        start, end = event[1], event[1] + event[2]
        while stack and stack[-1][0][1] + stack[-1][0][2] <= start:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack and end <= stack[-1][0][1] + stack[-1][0][2]:
            stack[-1][1] = True
        stack.append([event, False])
    leaves.extend(e for e, has_child in stack if not has_child)
    return leaves


def program_name(event_name: str) -> str:
    """``jit_decode_burst(123)`` -> ``jit_decode_burst``."""
    match = _PROGRAM.match(event_name)
    return match.group(1) if match else event_name


_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _is_collective(name: str) -> bool:
    """By the operation's own name or the computation it calls: on the
    chip an event's name is the whole HLO line, where
    ``%fusion.342 = ... fusion(...), kind=kCustom,
    calls=%all-reduce-scatter.clone`` is a collective and a fusion that
    merely *reads* ``%all-gather.3`` is compute."""
    return bool(_COLLECTIVE.search(_op_kind(name)))


def _op_kind(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion``: operations
    grouped by what they are, not by their number."""
    called = _CALLS.search(name)
    if called and _COLLECTIVE.search(called.group(1)):
        # a fusion around a collective: named for what it calls
        return re.sub(r"(\.clone|[.\d])+$", "", called.group(1)) + "_fusion"
    name = name.lstrip("%").split(" ")[0].split("=")[0]
    return re.sub(r"[.\d]+$", "", name) or name


def reduce_events(recorded: dict) -> dict:
    """Busy time, programs, operations, collectives and gaps of a recorded
    trace. Every time is in seconds; ``busy_s`` and the rest are averaged
    over the devices that ran anything."""
    devices = [d for d in recorded["devices"] if d["ops"] or d["modules"]]
    if not devices:
        return {}
    starts = [min(e[1] for e in d["ops"] or d["modules"]) for d in devices]
    ends = [max(e[1] + e[2] for e in d["ops"] or d["modules"])
            for d in devices]
    span = (min(starts), max(ends))
    if recorded.get("window"):     # everything the profiler saw, host too
        span = (min(span[0], recorded["window"][0]),
                max(span[1], recorded["window"][1]))
    busy, exposed, collective = [], [], []
    programs: Dict[str, List[float]] = {}
    program_runs: Dict[str, int] = {}
    kinds: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []   # (length, start, end)
    for device in devices:
        ops = device["ops"] or device["modules"]
        covered = _union([(e[1], e[1] + e[2]) for e in ops])
        busy.append(_length(covered))
        leaves = _leaves(device["ops"])
        # a collective is a leaf of the operation line (the core waits in
        # it) or an event of the asynchronous line (start to done, which
        # compute may overlap)
        coll = _union([(e[1], e[1] + e[2])
                       for e in leaves + device.get("async", [])
                       if _is_collective(e[0])])
        work = _union([(e[1], e[1] + e[2]) for e in leaves
                       if not _is_collective(e[0])])
        collective.append(_length(coll))
        exposed.append(_length(_subtract(coll, work)))
        for e in leaves:
            kind = _op_kind(e[0])
            kinds[kind] = kinds.get(kind, 0.0) + e[2] / len(devices)
        for e in device["modules"]:
            name = program_name(e[0])
            programs.setdefault(name, []).append(e[2])
        if device is devices[0]:
            for e in device["modules"]:
                name = program_name(e[0])
                program_runs[name] = program_runs.get(name, 0) + 1
            cursor = span[0]
            for start, end in covered + [(span[1], span[1])]:
                if start > cursor:
                    gaps.append((start - cursor, cursor, start))
                cursor = max(cursor, end)
    n = len(devices)
    ranked = sorted(kinds.items(), key=lambda kv: -kv[1])
    # the ten largest, then every named kernel behind them, by seconds: a
    # kernel that ran for 20 ms of a stretch is still what its reader reads
    kept = ranked[:_TOP] + [kv for kv in ranked[_TOP:]
                            if kv[0].startswith(_NAMED)]
    host = recorded.get("host", [])
    named_gaps: Dict[str, float] = {}
    for length, start, end in sorted(gaps, reverse=True)[:50]:
        named = _host_during(host, start, end)
        named_gaps[named] = named_gaps.get(named, 0.0) + length
    ns = 1e-9
    return {
        "span_s": (span[1] - span[0]) * ns,
        "devices": n,
        "busy_s": sum(busy) / n * ns,
        "collective_s": sum(collective) / n * ns,
        "collective_exposed_s": sum(exposed) / n * ns,
        "programs": {name: {"seconds": sum(durs) / n * ns,
                            "runs": program_runs.get(name, 0)}
                     for name, durs in programs.items()},
        "device_ops": [[k, v * ns] for k, v in kept],
        "idle_gaps": [[k, v * ns] for k, v in sorted(
            named_gaps.items(), key=lambda kv: -kv[1])[:_TOP]],
        "longest_gap_s": max((g[0] for g in gaps), default=0.0) * ns,
    }


def _host_during(host: list, start: float, end: float) -> str:
    """What the host was doing in a device idle gap: the host event that
    overlaps it most, among those not much longer than the gap (a span
    that covers the whole run explains nothing)."""
    best, best_overlap = "host: nothing recorded", 0.0
    limit = 20.0 * (end - start)
    for name, s, d in host:
        if d > limit:
            continue
        overlap = min(end, s + d) - max(start, s)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best[:80]


def reduce_directory(trace_dir: str) -> dict:
    """Load and reduce the newest trace under ``trace_dir``; the plane and
    line names ride along so that a reader can see what the trace held."""
    recorded = load_xplane(find_xplane(trace_dir))
    reduced = reduce_events(recorded)
    reduced["lines"] = recorded["lines"]
    reduced["sample"] = sample_of(recorded)
    return reduced


def sample_of(recorded: dict, length_ns: float = 30e6,
              most: int = 1500) -> dict:
    """A short stretch from the middle of the first device's lines, raw,
    for reading by hand (``--detail``) and for the recorded trace the
    tests keep. Never printed in the result line."""
    devices = recorded["devices"]
    if not devices or not devices[0]["ops"]:
        return {}
    device = devices[0]
    starts = [e[1] for e in device["ops"]]
    lo = (min(starts) + max(starts)) / 2
    hi = lo + length_ns

    def inside(events):
        return [e for e in events if e[1] < hi and e[1] + e[2] > lo][:most]

    return {"name": device["name"], "from_ns": lo, "to_ns": hi,
            "ops": inside(device["ops"]),
            "modules": inside(device["modules"]),
            "async": inside(device.get("async", []))}
