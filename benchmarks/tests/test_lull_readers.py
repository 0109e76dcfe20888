"""``lull_share.serve`` and ``loaded_idle.serve`` by hand: on a small
recorded trace with a lull in it (``recorded_lull_trace.json``, through
the unedited ``trace.reduce_events``), on the chip's reduced trace of
PR 24 (``recorded_rt_gaps.json``: no lull there), on what the parent's
program gives them (no ``rt.pump.lull``, no ``build`` phases) and on
every recorded run: a number wherever ``device_idle.serve`` reads one,
None only where it reads None."""

import importlib.util
import json
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAME == name and module.KINDS == ("serve",)
    return module


LULL = _reader("lull_share.serve")
LOADED = _reader("loaded_idle.serve")
IDLE = _reader("device_idle.serve")
ATTRIBUTED = _reader("idle_attributed.serve")
HOST_GAP = _reader("engine_host_gap_ms")


def _recorded(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


@pytest.fixture()
def recorded():
    return _recorded("recorded_lull_trace.json")


def test_the_long_gap_is_the_lull_and_the_seam_is_the_build(recorded):
    reduced = trace.reduce_events(recorded)
    assert reduced["span_s"] == pytest.approx(1.405)
    assert reduced["busy_s"] == pytest.approx(0.4)
    # a piece of 50 ms beats the 6 ms of prefill.build and the 3 ms by
    # which prefill.dispatch overlaps the gap's end; the seam has 3 of
    # its 5 ms under decode.build
    assert reduced["idle_gaps"] == [
        ["rt.pump.lull", pytest.approx(1.0)],
        ["rt.engine.decode.build", pytest.approx(0.005)]]
    run = {"trace": reduced}
    assert LULL.compute(run) == pytest.approx(100 * 1.0 / 1.405)
    assert LOADED.compute(run) == pytest.approx(100 * 0.005 / 0.405)
    assert IDLE.compute(run) == pytest.approx(100 * 1.005 / 1.405)
    # the accepted readers mend themselves: the lull is attributed, and
    # is no host cost of the three dispatches
    assert ATTRIBUTED.compute(run) == pytest.approx(100.0)
    assert HOST_GAP.compute(run) == pytest.approx(1e3 * 0.005 / 3)


def test_the_parents_program_reads_zero_and_device_idle(recorded):
    """No ``rt.pump.lull`` and one ``dispatch`` over both halves: the
    long gap takes the next request's ``prefill.dispatch`` for its name,
    as in the ledger's lines of PR 37."""
    host = [e for e in recorded["host"] if e[0] != "rt.pump.lull"
            and not e[0].endswith(".build")]
    for event in host:
        if event[0] == "rt.engine.prefill.dispatch":
            event[1:] = [1091e6, 16e6]
        if event[0] == "rt.engine.decode.dispatch":
            event[1:] = [1301e6, 5.5e6]
    reduced = trace.reduce_events(dict(recorded, host=host))
    assert reduced["idle_gaps"] == [
        ["rt.engine.prefill.dispatch", pytest.approx(1.0)],
        ["rt.engine.decode.dispatch", pytest.approx(0.005)]]
    run = {"trace": reduced}
    assert LULL.compute(run) == 0.0
    assert LOADED.compute(run) == pytest.approx(IDLE.compute(run))
    assert HOST_GAP.compute(run) == pytest.approx(1e3 * 1.005 / 3)


def test_on_the_chips_trace_without_a_lull():
    run = {"trace": _recorded("recorded_rt_gaps.json")}
    assert LULL.compute(run) == 0.0
    assert LOADED.compute(run) == pytest.approx(IDLE.compute(run))
    assert LOADED.compute(run) == pytest.approx(
        100 * (1 - 3.7668055330000003 / 4.006497136))
    # 0.1 s of the stretch's 0.24 idle seconds named a lull: the rest of
    # the idle time over the rest of the stretch
    run["trace"]["idle_gaps"].append(["rt.pump.lull", 0.1])
    assert LULL.compute(run) == pytest.approx(100 * 0.1 / 4.006497136)
    assert LOADED.compute(run) == pytest.approx(
        100 * (4.006497136 - 0.1 - 3.7668055330000003)
        / (4.006497136 - 0.1))


def test_a_stretch_that_is_all_lull_reads_zero_not_none():
    run = {"trace": {"span_s": 2.0, "busy_s": 0.0,
                     "idle_gaps": [["rt.pump.lull", 2.0]]}}
    assert LULL.compute(run) == 100.0
    assert LOADED.compute(run) == 0.0


@pytest.mark.parametrize("run", [
    {}, {"trace": None}, {"trace": {}}, {"trace": {"idle_gaps": []}},
    {"trace": {"span_s": 0.0, "busy_s": 0.0}}])
def test_none_only_where_device_idle_is_none(run):
    assert IDLE.compute(run) is None
    assert LULL.compute(run) is None and LOADED.compute(run) is None


@pytest.mark.parametrize("trace_of", [
    lambda: _recorded("recorded_rt_gaps.json"),
    lambda: _recorded("recorded_olmoe_run.json")["trace"],
    lambda: _recorded("recorded_deepseek_v2_run.json")["trace"],
    lambda: {"span_s": 4.0, "busy_s": 3.7},
    lambda: {"span_s": 4.0, "busy_s": 3.7, "idle_gaps": None}],
    ids=["rt_gaps", "olmoe_run", "deepseek_v2_run", "no-gaps", "gaps-none"])
def test_a_number_on_every_recorded_run_that_has_a_stretch(trace_of):
    run = {"trace": trace_of()}
    assert run["trace"]["span_s"] > 0
    assert LULL.compute(run) == 0.0
    assert LOADED.compute(run) == pytest.approx(IDLE.compute(run))
