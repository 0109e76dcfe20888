"""Run one cell of the benchmark once and print its result.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``benchmarks/workloads/<cell>.json``; it names a configuration
(``benchmarks/configs/``) and a traffic mix (``benchmarks/traffic/``).
Everything before the window opens is set-up; the last line of standard
output is one JSON object (see ``benchmarks/README.md``). Without the
cell's TPU chips nothing is printed and the exit code is not 0; only a
``rehearsal`` configuration runs on the CPU, and its line says ``cpu``.
This process never imports jax: the chip belongs to the replica or the
gang worker that the runtime starts.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()      # set-up counts from here

import argparse              # noqa: E402
import importlib.util        # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import shutil                # noqa: E402
import signal                # noqa: E402
import sys                   # noqa: E402
import tempfile              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"no such file: {path}") from None


def load_readers(kind: str, declared=None) -> list:
    """The per-layer readers under ``layer_metrics/`` that serve this
    kind of cell, found by listing the directory: a later PR adds a file
    and edits none. For a cell of ``BENCHMARK.json`` only those it
    declares are loaded: another cell's reader, which may not know this
    cell's family or program, is never called on it."""
    readers = []
    directory = os.path.join(HERE, "layer_metrics")
    for filename in sorted(os.listdir(directory)):
        if not filename.endswith(".py") or (
                declared is not None and filename[:-3] not in declared):
            continue
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + filename[:-3].replace(".", "_"),
            os.path.join(directory, filename))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if module.NAME != filename[:-3]:
            raise SystemExit(f"{filename} defines metric {module.NAME!r}")
        if kind in module.KINDS:
            readers.append(module)
    return readers


def declared_metrics(cell_name: str, group: str):
    """Names ``BENCHMARK.json`` declares for this cell under ``group``
    (``end_to_end`` or ``per_layer``), or None where the file or the cell
    is not there (a rehearsal, a cell being written): then every metric
    that can be computed is printed."""
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    except FileNotFoundError:
        return None
    if cell_name not in {w["name"] for w in bench["workloads"]}:
        return None
    return {m["name"] for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])}


def per_layer(run: dict, kind: str, declared=None) -> dict:
    """What each reader of this cell found in the run; a reader that
    found nothing is left out."""
    metrics = {}
    for reader in load_readers(kind, declared):
        value = reader.compute(run)
        if value is not None:
            metrics[reader.NAME] = {"value": value, "unit": reader.UNIT}
    return metrics


def held_to_declared(cell_name: str, metrics: dict, declared,
                     rehearsal: bool) -> dict:
    """A declared cell prints the metrics declared for it and no other.
    A reader that found nothing to read (a renamed seam, a trace without
    the program) may not drop a declared metric in silence: no result,
    and the names of what is missing."""
    if declared is None:
        return metrics
    missing = sorted(declared - set(metrics))
    if missing and not rehearsal:
        raise SystemExit(f"cell {cell_name}: BENCHMARK.json declares "
                         f"{missing}, and this run could not read them")
    return {k: v for k, v in metrics.items() if k in declared}


def breakdown_of(trace: dict) -> dict:
    """At most ten device entries: the programs that took most device
    time (``program:`` before their name), then kinds of operation."""
    programs = sorted(((f"program:{name}", p["seconds"]) for name, p in
                       (trace.get("programs") or {}).items()),
                      key=lambda kv: -kv[1])[:4]
    ops = [tuple(kv) for kv in trace.get("device_ops", [])][:10 - len(programs)]
    return {"device_ops": [list(kv) for kv in programs + ops],
            "idle_gaps": [list(kv) for kv in trace.get("idle_gaps", [])][:10]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--detail", help="also write the run's notes and "
                        "raw records to this JSON file")
    args = parser.parse_args(argv)

    os.chdir(ROOT)               # workers inherit the working directory
    sys.path.insert(0, ROOT)     # ... and import benchmarks.harness from it
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    if cell["kind"] != config["kind"] or cell["kind"] != mix["kind"]:
        raise SystemExit(f"cell {cell['name']} is a {cell['kind']} cell but "
                         f"its configuration or traffic is not")
    config_path = os.path.join(HERE, "configs", cell["config"] + ".json")
    rehearsal = bool(config.get("rehearsal"))

    try:
        import ray_tpu
    except ImportError as e:
        raise SystemExit(f"the system under test is not here: {e}") from None
    from benchmarks.harness import families, runtime, serve_cell, train_cell

    try:
        families.family_of(config)   # before anything is started
    except ValueError as e:
        raise SystemExit(str(e)) from None
    scratch = tempfile.mkdtemp(prefix="ray_tpu_bench_")
    kind = {"serve": serve_cell, "train": train_cell}[cell["kind"]]
    # a driver that stops this run sends SIGTERM: leave by the same door
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        detected = runtime.start_runtime(int(cell["chips"]), rehearsal)
        if cell["kind"] == "serve":
            run = serve_cell.run(cell, config, mix, config_path, args.seed,
                                 args.seconds, bool(args.trace), scratch,
                                 T_PROCESS)
        else:
            run = train_cell.run(cell, config, mix, args.seed, args.seconds,
                                 bool(args.trace), scratch, T_PROCESS,
                                 detected)
    except BaseException as e:
        try:
            runtime.dump_worker_logs()
        finally:
            print(f"cell {cell['name']} did not run to its end: "
                  f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        raise
    finally:
        try:
            at_exit = runtime.stop_runtime()
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
    if "jax" in sys.modules:
        raise SystemExit("the driver imported jax")

    run.update(config=config, mix=mix, cell=cell)
    device = run["device"]
    if not rehearsal and device["platform"] != "tpu":
        raise SystemExit(f"not a TPU run: {device}")
    declared = declared_metrics(
        cell["name"], "per_layer" if args.trace else "end_to_end")
    if args.trace:
        metrics = per_layer(run, cell["kind"], declared)
    else:
        metrics = {name: {"value": value[0], "unit": value[1]}
                   for name, value in kind.end_to_end(run).items()
                   if value is not None}
    metrics = held_to_declared(cell["name"], metrics, declared, rehearsal)
    result = {
        "correct": run["correct"], "attempted": run["attempted"],
        "failed": run["failed"], "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device["memory_peak_bytes"]},
    }
    trace = run.get("trace") or {}
    if args.trace and trace.get("span_s"):
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["span_s"]
        result["breakdown"] = breakdown_of(trace)
    result["notes"] = dict(run["notes"], exit=at_exit)
    if args.detail:
        os.makedirs(os.path.dirname(os.path.abspath(args.detail)),
                    exist_ok=True)
        with open(args.detail, "w") as f:
            json.dump({**result, "run": {k: run[k] for k in (
                "client", "engine", "steps", "trace", "window_s")
                if k in run}}, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
