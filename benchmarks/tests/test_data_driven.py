"""A later PR adds a cell, a traffic mix, a configuration, its family and
a per-layer metric as new files, and edits none that is there: shown on
a temporary copy of the benchmark's directory, run end to end at toy
size on the CPU.
"""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

NEW_READER = '''"""Engine: requests the window finished, a count."""
NAME, UNIT, SOURCE = "finished_requests", "requests", "program_counter"
LAYER, MOVES, KINDS = "LLM replica and engine", "serve_tok_s", ("serve",)


def compute(run):
    return float(len(run["engine"]["finished"]))
'''

# what a ``model_config`` PR brings for another architecture; here the
# dense block under another name, with a key and a limit of its own
NEW_FAMILY = '''"""The dense family again, as a later PR's file."""
from benchmarks.families.llama_dense import *  # noqa: F401,F403
from benchmarks.families import llama_dense

CONFIG_KEYS = llama_dense.CONFIG_KEYS | {"sliding_window"}
MARGIN_LIMIT = 0.125      # its own, so that the run shows whose it used
'''


def test_new_files_are_found_and_run(tmp_path):
    copy = tmp_path / "benchmarks"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}

    config = json.loads((copy / "configs" / "tiny-rehearsal.json").read_text())
    config.update(name="tiny-other", num_hidden_layers=3,
                  family="other_family", sliding_window=None)
    (copy / "families" / "other_family.py").write_text(NEW_FAMILY)
    (copy / "configs" / "tiny-other.json").write_text(json.dumps(config))
    mix = json.loads((copy / "traffic" / "tiny-chat.json").read_text())
    mix.update(name="tiny-bursty", arrivals="gamma", arrival_cv=3.0,
               cycle_requests=18)
    (copy / "traffic" / "tiny-bursty.json").write_text(json.dumps(mix))
    cell = json.loads((copy / "workloads" / "tiny-chat.json").read_text())
    cell.update(name="tiny-other-bursty", config="tiny-other",
                traffic="tiny-bursty", rate_rps=6.0)
    (copy / "workloads" / "tiny-other-bursty.json").write_text(
        json.dumps(cell))
    (copy / "layer_metrics" / "finished_requests.py").write_text(NEW_READER)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, str(copy / "run.py"), "--workload",
         "tiny-other-bursty", "--seed", str(2**31 + 5), "--seconds", "3",
         "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 18                 # 6 a second x 3 s
    assert result["device"]["platform"] == "cpu"     # and it says so
    assert result["metrics"]["finished_requests"]["value"] >= 18
    assert "window_compiles" in result["metrics"]
    assert result["notes"]["probes"]["margin_limit"] == 0.125
    # nothing that was there was edited
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_family_that_is_not_there_is_named(tmp_path):
    copy = tmp_path / "benchmarks"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    config = json.loads((copy / "configs" / "tiny-rehearsal.json").read_text())
    config.update(name="tiny-lost", family="not_written_yet")
    (copy / "configs" / "tiny-lost.json").write_text(json.dumps(config))
    cell = json.loads((copy / "workloads" / "tiny-chat.json").read_text())
    cell.update(name="tiny-lost-chat", config="tiny-lost")
    (copy / "workloads" / "tiny-lost-chat.json").write_text(json.dumps(cell))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, str(copy / "run.py"), "--workload",
         "tiny-lost-chat", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and not out.stdout.strip()
    assert "benchmarks/families/not_written_yet.py" in out.stderr


def test_no_result_without_the_system(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files there is nothing to measure: no result, not exit code 0."""
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "mistral7b-chat-steady", "--seed", "1", "--seconds", "2",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_a_real_cell_prints_nothing_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "mistral7b-train-fsdp2tp2", "--seed", "1", "--seconds", "2",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_benchmark_json_matches_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads", cell["name"] + ".json")) as f:
            data = json.load(f)
        assert (data["config"], data["traffic"], data["chips"]) == (
            cell["config"], cell["traffic"], cell["chips"])
        with open(os.path.join(ROOT, next(
                c["file"] for c in bench["configs"]
                if c["name"] == cell["config"]))) as f:
            assert not json.load(f).get("rehearsal")
    for metric in bench["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", metric["name"] + ".py")
        text = open(path).read()
        for key in ("unit", "layer", "moves", "source"):
            assert f'"{metric[key]}"' in text, (metric["name"], key)
