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

import pytest

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


RAISING_READER = '''"""Another family's reader: it knows nothing of this cell's run."""
NAME, UNIT, SOURCE = "other_cells_share", "%", "program_counter"
LAYER, MOVES, KINDS = "LLM replica and engine", "serve_tok_s", ("serve",)


def compute(run):
    raise KeyError("this run has no such counter")
'''

SILENT_READER = '''"""A reader whose seam was renamed: nothing to read."""
NAME, UNIT, SOURCE = "renamed_seam_ms", "ms", "program_span"
LAYER, MOVES, KINDS = "LLM replica and engine", "serve_tok_s", ("serve",)


def compute(run):
    return None
'''


def _declare(root, cell_name, per_layer):
    """A BENCHMARK.json beside the copy that lists one cell and the
    per-layer metrics it declares (only the keys ``run.py`` reads)."""
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": cell_name}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": name, "workloads": [cell_name]}
                      for name in per_layer]
        + [{"name": "other_cells_share", "workloads": ["another-cell"]}]}))


def _load_run(copy):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "copied_benchmarks_run", copy / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_declared_cell_computes_only_its_own_readers(tmp_path):
    """A later PR's reader that raises on another family's run does not
    stop a cell that does not declare it (the parent is run under the
    PR's files); a rehearsal, which declares nothing, calls every reader
    of its kind and meets the error."""
    copy = tmp_path / "benchmarks"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (copy / "layer_metrics" / "finished_requests.py").write_text(NEW_READER)
    (copy / "layer_metrics" / "other_cells_share.py").write_text(
        RAISING_READER)
    _declare(tmp_path, "tiny-chat", ["finished_requests", "window_compiles"])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, str(copy / "run.py"), "--workload", "tiny-chat",
         "--seed", str(2**31 + 9), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert sorted(result["metrics"]) == ["finished_requests",
                                         "window_compiles"]
    # a window of 3 s traces a quarter in, as before, and says so
    stretch = result["notes"]["traced_stretch"]
    assert stretch["opens_s"] == 0.75 and 0.89 < stretch["for_s"] < 0.91
    run = _load_run(copy)
    assert [r.NAME for r in run.load_readers(
        "serve", {"finished_requests", "window_compiles"})] == [
        "finished_requests", "window_compiles"]
    names = [r.NAME for r in run.load_readers("serve")]
    assert "other_cells_share" in names and len(names) > 10
    with pytest.raises(KeyError):      # declared, it is called
        run.per_layer({"engine": {"finished": []}}, "serve",
                      {"other_cells_share"})


def test_a_declared_reader_that_finds_nothing_stops_the_run_by_name(
        tmp_path):
    copy = tmp_path / "benchmarks"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (copy / "layer_metrics" / "finished_requests.py").write_text(NEW_READER)
    (copy / "layer_metrics" / "renamed_seam_ms.py").write_text(SILENT_READER)
    (copy / "layer_metrics" / "other_cells_share.py").write_text(
        RAISING_READER)
    run = _load_run(copy)
    declared = {"finished_requests", "renamed_seam_ms"}
    found = run.per_layer({"engine": {"finished": [1, 2]}}, "serve", declared)
    assert found == {"finished_requests": {"value": 2.0,
                                           "unit": "requests"}}
    with pytest.raises(SystemExit) as stopped:
        run.held_to_declared("a-cell", found, declared, rehearsal=False)
    said = str(stopped.value)     # a string: the exit code is 1
    assert "renamed_seam_ms" in said and "a-cell" in said
    assert "finished_requests" not in said
    # a rehearsal on the CPU cannot read a share of a TPU's peak: it
    # prints what it has; a cell that declares nothing prints everything
    assert run.held_to_declared("a-cell", found, declared,
                                rehearsal=True) == found
    extra = dict(found, other={"value": 1.0, "unit": "x"})
    assert run.held_to_declared("a-cell", extra, None, False) == extra
    assert run.held_to_declared("a-cell", extra,
                                {"finished_requests"}, False) == {
        "finished_requests": found["finished_requests"]}


def test_the_breakdown_prints_ten_entries_whatever_the_trace_keeps():
    from pathlib import Path

    run = _load_run(Path(BENCH))
    kept = [[f"op{i}", 100.0 - i] for i in range(10)] + [
        ["flash_mla_fwd", 0.03], ["rt_mla_decode", 0.02]]
    trace = {"programs": {"jit_decode_burst": {"seconds": 2.0, "runs": 9},
                          "jit_prefill_sample": {"seconds": 1.0, "runs": 3}},
             "device_ops": kept, "idle_gaps": [["rt.pump.lull", 1.0]]}
    out = run.breakdown_of(trace)
    assert out["device_ops"] == [["program:jit_decode_burst", 2.0],
                                 ["program:jit_prefill_sample", 1.0]] + kept[:8]
    assert out["idle_gaps"] == [["rt.pump.lull", 1.0]]


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
