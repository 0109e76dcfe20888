"""The trace reduction on a small recorded trace with answers worked by
hand (``recorded_trace.json``: two devices, times in nanoseconds)."""

import json
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_busy_programs_collectives_and_gaps(recorded):
    out = trace.reduce_events(recorded)
    ns = 1e-9
    assert out["devices"] == 2
    assert out["span_s"] == pytest.approx(5000 * ns)
    # device 0: [0, 900] + [3000, 4000] = 1900; device 1: [0, 1200]
    assert out["busy_s"] == pytest.approx((1900 + 1200) / 2 * ns)
    # leaves only: the while is not a collective and not counted twice
    assert out["collective_s"] == pytest.approx((200 + 600) / 2 * ns)
    assert out["collective_exposed_s"] == pytest.approx((200 + 600) / 2 * ns)
    programs = out["programs"]
    assert programs["jit_decode_burst"]["seconds"] == pytest.approx(
        (1000 + 1200) / 2 * ns)
    assert programs["jit_decode_burst"]["runs"] == 1      # per device
    assert programs["jit_prefill_sample"]["seconds"] == pytest.approx(
        1000 / 2 * ns)
    kinds = dict(out["device_ops"])
    assert kinds["fusion"] == pytest.approx((300 + 250 + 1000 + 600) / 2 * ns)
    assert "while" not in kinds
    # device 0's gaps: 900..3000 (the host was in engine.sync) and
    # 4000..5000 (nothing short enough was recorded)
    gaps = dict(out["idle_gaps"])
    assert gaps["engine.sync"] == pytest.approx(2100 * ns)
    assert out["longest_gap_s"] == pytest.approx(2100 * ns)
    assert sum(gaps.values()) == pytest.approx((2100 + 1000) * ns)


def test_overlapping_compute_hides_a_collective():
    recorded = {"devices": [{"name": "/device:TPU:0", "modules": [], "ops": [
        ["all-reduce.1", 0, 1000], ["fusion.1", 200, 300]]}], "host": []}
    # on one line these would nest; as intervals the compute covers 300
    # of the collective's 1000
    out = trace.reduce_events(recorded)
    assert out["collective_exposed_s"] == pytest.approx(0.0)  # a parent
    recorded["devices"][0]["ops"] = [["all-reduce.1", 0, 1000],
                                     ["fusion.1", 1000, 300]]
    out = trace.reduce_events(recorded)
    assert out["collective_exposed_s"] == pytest.approx(1000e-9)


def test_an_asynchronous_collective_is_exposed_where_nothing_computes():
    recorded = {"devices": [{"name": "/device:TPU:0", "modules": [], "ops": [
        ["fusion.1", 0, 400], ["all-gather-done.1", 700, 300]],
        "async": [["all-gather-start.1", 100, 900]]}], "host": []}
    out = trace.reduce_events(recorded)
    # in flight 100..1000, compute covers 100..400 of it
    assert out["collective_s"] == pytest.approx(900e-9)
    assert out["collective_exposed_s"] == pytest.approx(600e-9)


def test_a_stretch_recorded_on_the_chip():
    """12 ms of a four-chip train step as the profiler wrote it: names
    are whole HLO lines, collectives hide inside fusions."""
    with open(os.path.join(HERE, "recorded_tpu_sample.json")) as f:
        recorded = json.load(f)
    ops = recorded["devices"][0]["ops"]
    calls = [e[0] for e in ops if "calls=%all-reduce-scatter" in e[0]]
    reads = [e[0] for e in ops if "all-gather" in e[0]
             and "all-gather" not in e[0].split(" = ")[0]
             and "calls=%all" not in e[0]]
    assert calls and reads
    assert all(trace._is_collective(name) for name in calls)
    assert not any(trace._is_collective(name) for name in reads)
    assert trace._op_kind(calls[0]) == "all-reduce-scatter_fusion"
    out = trace.reduce_events(recorded)
    assert out["busy_s"] <= out["span_s"]
    assert out["busy_s"] / out["span_s"] > 0.99
    # reduce-scatter fusions 2.37 ms, all-reduce 0.75, all-gather 0.68 of
    # 13.3 ms, one after another on the operation line: all exposed
    assert 0.25 < out["collective_exposed_s"] / out["span_s"] < 0.33
    kinds = dict(out["device_ops"])
    assert kinds["all-reduce-scatter_fusion"] == pytest.approx(2.365e-3,
                                                               rel=0.01)


def test_a_named_kernel_is_kept_whatever_its_rank():
    """Fourteen kinds of operation, the program's own kernels twelfth to
    fourteenth: the ten largest come first and unchanged, the named ones
    behind them in order of seconds, and nothing else."""
    plain = [f"op{chr(97 + i)}" for i in range(11)]      # opa .. opk
    kinds = ([(name, 1000 - 10 * i) for i, name in enumerate(plain)]
             + [("rt_mla_decode", 21), ("flash_mla_fwd", 30),
                ("rt.moe.route", 5), ("convert", 4)])
    ops, cursor = [], 0
    for name, length in kinds:
        ops.append([f"%{name}.{cursor} = bf16[8]", cursor, length])
        cursor += length + 5
    out = trace.reduce_events({"devices": [
        {"name": "/device:TPU:0", "modules": [], "ops": ops}], "host": []})
    listed = out["device_ops"]
    assert [k for k, _ in listed[:10]] == plain[:10]
    assert [k for k, _ in listed[10:]] == [
        "flash_mla_fwd", "rt_mla_decode", "rt.moe.route"]
    assert dict(listed)["rt_mla_decode"] == pytest.approx(21e-9)
    # a named kernel among the ten largest is listed once, in its place
    ops.append(["%flash_mla_fwd.9 = bf16[8]", cursor, 5000])
    listed = trace.reduce_events({"devices": [
        {"name": "/device:TPU:0", "modules": [], "ops": ops}],
        "host": []})["device_ops"]
    assert listed[0] == ["flash_mla_fwd", pytest.approx(5030e-9)]
    assert [k for k, _ in listed[1:10]] == plain[:9]
    assert [k for k, _ in listed[10:]] == ["rt_mla_decode", "rt.moe.route"]


def test_interval_arithmetic():
    assert trace._union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace._subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert trace.program_name("jit_decode_burst(123)") == "jit_decode_burst"
    assert trace._op_kind("%fusion.123 = bf16[8]") == "fusion"
    assert trace._op_kind("all-gather-start.4") == "all-gather-start"


def test_an_empty_trace_gives_nothing():
    assert trace.reduce_events({"devices": [], "host": []}) == {}
