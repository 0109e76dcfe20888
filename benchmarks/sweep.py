"""Find the highest rate a serve cell's tree sustains: one deployment,
one measured window per rate, one JSON line per rate.

    python3 benchmarks/sweep.py --workload <cell> --rates 2,3,4,5,6,7,8,10 --seconds 30 --seed <n>

Run once, by hand, on the chip, when a cell is defined (or when an
optimisation has moved its knee); the cell's file then gets 0.8 of the
highest sustained rate as a number. A rate is *sustained* when at least
98% of the requests due in the first four fifths of the window finish
inside the window, and the median TTFT of the window's last third is at
most 1.5 times that of its first third. A benchmark run never searches:
it offers the rate its cell's file states.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse              # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import shutil                # noqa: E402
import sys                   # noqa: E402
import tempfile              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def sustained(run: dict) -> dict:
    from benchmarks.harness import stats

    window = run["window_s"]
    counted = [r for r in run["client"] if r["counted"]]
    early = [r for r in counted if r["due"] <= 0.8 * window]
    done = [r for r in early if r["ok"] and r["done"] <= window]
    ttft = lambda rs: [r["first"] - r["due"] for r in rs
                       if r["first"] is not None]
    first = stats.median(ttft([r for r in counted
                               if r["due"] <= window / 3]))
    last = stats.median(ttft([r for r in counted
                              if r["due"] > 2 * window / 3]))
    share = len(done) / len(early) if early else 0.0
    growth = last / first if first and last else float("inf")
    return {"finished_share_of_first_four_fifths": share,
            "ttft_median_first_third_ms": 1e3 * first if first else None,
            "ttft_median_last_third_ms": 1e3 * last if last else None,
            "ttft_growth": growth,
            "sustained": share >= 0.98 and growth <= 1.5}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import run as bench_run

    import ray_tpu
    from benchmarks.harness import runtime, serve_cell

    cell = bench_run.load_json("workloads", args.workload + ".json")
    config = bench_run.load_json("configs", cell["config"] + ".json")
    mix = bench_run.load_json("traffic", cell["traffic"] + ".json")
    scratch = tempfile.mkdtemp(prefix="ray_tpu_bench_")
    try:
        runtime.start_runtime(int(cell["chips"]),
                              bool(config.get("rehearsal")))
        serving = serve_cell.ServeCell(
            cell, config, mix,
            os.path.join(HERE, "configs", cell["config"] + ".json"),
            args.seed, scratch, T_PROCESS)
        stats_call = serving.handle.options(method_name="stats")
        try:
            for rate in (float(r) for r in args.rates.split(",")):
                run = serving.measure(args.seconds, False, rate_rps=rate)
                run.update(config=config, mix=mix, cell=cell)
                e2e = serve_cell.end_to_end(run)
                samples = run["engine"]["occupancy"]
                print(json.dumps({
                    "rate_rps": rate, "attempted": run["attempted"],
                    "failed": run["failed"], **sustained(run),
                    # lead-in included: a shape first met at this rate
                    "compiles": samples[-1]["compiles"]
                    - samples[0]["compiles"] if samples else None,
                    **{k: v[0] for k, v in e2e.items() if v and k != "setup_s"},
                    "device": run["device"]["platform"]}), flush=True)
                # let what the tail left in the engine run out
                deadline = time.time() + 60
                while time.time() < deadline:
                    s = ray_tpu.get(stats_call.remote(), timeout=60)
                    if not s["running"] and not s["waiting"]:
                        break
                    time.sleep(0.5)
        finally:
            serving.close()
    except BaseException:
        runtime.dump_worker_logs()
        raise
    finally:
        try:
            runtime.stop_runtime()
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
