"""The two readers of the program's ``rt.*`` spans on a reduced trace
recorded on the chip (``recorded_rt_gaps.json``: PR 24's traced run of
the chat cell), with the answers worked by hand, and on what a program
without spans, a run without a trace and an idle pump give them."""

import copy
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAME == name
    return module


ATTRIBUTED = _reader("idle_attributed.serve")
HOST_GAP = _reader("engine_host_gap_ms")


@pytest.fixture()
def run():
    with open(os.path.join(HERE, "recorded_rt_gaps.json")) as f:
        return {"trace": json.load(f)}


def test_on_the_chips_trace(run):
    # every listed gap carries a phase's name: 0.0984 + 0.0545 + 0.0510
    # + 0.0322 s, over 35 decode bursts and 16 prefills (the 16 runs of
    # jit_convert_element_type are not dispatches of the round)
    assert ATTRIBUTED.compute(run) == pytest.approx(100.0)
    assert HOST_GAP.compute(run) == pytest.approx(
        1e3 * (0.098439905 + 0.054507606 + 0.051011871 + 0.032241875)
        / (35 + 16))
    assert HOST_GAP.compute(run) == pytest.approx(4.6314, abs=1e-4)


def test_gaps_that_kept_jaxs_names_count_against(run):
    gaps = run["trace"]["idle_gaps"]
    gaps[1][0] = "np.asarray(jax.Array)"          # 0.0545 s of 0.2362
    assert ATTRIBUTED.compute(run) == pytest.approx(
        100.0 * (1 - 0.054507606 / 0.236201257))
    assert HOST_GAP.compute(run) == pytest.approx(
        1e3 * (0.236201257 - 0.054507606) / 51)


def test_a_program_without_spans_reads_zero(run):
    """The parent commit under this PR's benchmark files: gaps are
    there, none is ``rt.``; a declared metric may not read None."""
    run["trace"]["idle_gaps"] = [
        ["np.asarray(jax.Array)", 0.262953834], ["shard_args", 0.051443947],
        ["PjitFunction(convert_element_type)", 0.023289035]]
    assert ATTRIBUTED.compute(run) == 0.0
    assert HOST_GAP.compute(run) == 0.0


@pytest.mark.parametrize("trace", [None, {}, {"idle_gaps": []},
                                   {"span_s": 4.0, "busy_s": 3.7}])
def test_no_trace_or_no_gaps_reads_nothing(trace):
    assert ATTRIBUTED.compute({"trace": trace}) is None
    assert HOST_GAP.compute({"trace": trace}) is None
    assert ATTRIBUTED.compute({}) is None and HOST_GAP.compute({}) is None


def test_an_idle_pump_is_attributed_but_costs_nothing(run):
    """``rt.pump.idle``: the device waits because no request is there.
    The tracing saw it, and the host did not cause it."""
    only_idle = copy.deepcopy(run)
    only_idle["trace"]["idle_gaps"] = [["rt.pump.idle", 0.5]]
    assert ATTRIBUTED.compute(only_idle) == pytest.approx(100.0)
    assert HOST_GAP.compute(only_idle) == 0.0
    run["trace"]["idle_gaps"].append(["rt.pump.idle", 0.5])
    run["trace"]["idle_gaps"].append(["rt.pump.fanout", 0.051])
    assert ATTRIBUTED.compute(run) == pytest.approx(100.0)
    assert HOST_GAP.compute(run) == pytest.approx(
        1e3 * (0.236201257 + 0.051) / 51)


def test_programs_missing_reads_nothing_per_dispatch(run):
    del run["trace"]["programs"]        # the CPU rehearsal: no modules
    assert ATTRIBUTED.compute(run) == pytest.approx(100.0)
    assert HOST_GAP.compute(run) is None
