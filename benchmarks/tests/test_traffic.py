import json
import os
from collections import Counter

import pytest

from benchmarks.harness import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _mix(name="chat-steady"):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _schedule(seed, rate=5.6, window=40.0):
    return traffic.open_loop_schedule(_mix(), rate, window, 6.0, 20.0, seed,
                                      32768)


def test_same_seed_same_inputs_other_seed_other_inputs():
    a, b, c = _schedule(2**31 + 11), _schedule(2**31 + 11), _schedule(12)
    assert a == b
    assert [r.prompt_ids for r in a] != [r.prompt_ids for r in c]


def test_every_seed_does_the_same_work_in_another_order():
    a = [r for r in _schedule(1) if r.counted]
    b = [r for r in _schedule(2) if r.counted]
    sizes = lambda rs: sorted((len(r.prompt_ids), r.max_tokens) for r in rs)
    assert sizes(a) == sizes(b)
    assert [len(r.prompt_ids) for r in a] != [len(r.prompt_ids) for r in b]
    gaps = lambda rs: sorted(round(y.due_s - x.due_s, 9)
                             for x, y in zip(rs, rs[1:]))
    # one gap of the cycle lies across the window's edge; the rest agree
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 4


def test_due_times():
    reqs = _schedule(7)
    counted = [r for r in reqs if r.counted]
    assert len(counted) == 224      # 5.6 x 40: one whole turn of the cycle
    assert [r.index for r in counted] == list(range(224))
    assert 0 < counted[0].due_s and abs(counted[-1].due_s - 40.0) < 1e-9
    assert all(x.due_s <= y.due_s for x, y in zip(reqs, reqs[1:]))
    assert -6.0 < reqs[0].due_s < 0 and not reqs[0].counted   # lead-in
    assert 40.0 < reqs[-1].due_s <= 60.0 and not reqs[-1].counted  # tail
    assert [r.index for r in reqs] == list(range(reqs[0].index,
                                                 reqs[-1].index + 1))


def test_sizes_follow_the_mix():
    import statistics

    counted = [r for r in _schedule(3) if r.counted]
    prompts = [len(r.prompt_ids) for r in counted]
    outputs = [r.max_tokens for r in counted]
    assert min(prompts) >= 32 and max(prompts) <= 768
    assert min(outputs) >= 8 and max(outputs) <= 160
    assert statistics.median(prompts) == 192
    assert statistics.median(outputs) == 48
    assert all(1 <= t < 32768 for r in counted for t in r.prompt_ids)


def test_gaps_have_mean_one():
    import random

    for arrivals in ("poisson", "gamma"):
        gaps = traffic.stratified_gaps(arrivals, 123, random.Random(1),
                                       cv=3.0)
        assert len(gaps) == 123 and abs(sum(gaps) - 123.0) < 1e-9


def test_warm_up_meets_every_shape():
    engine = {"page_size": 64, "decode_burst": 8, "max_seq_len": 1024}
    reqs = traffic.warmup_requests(engine, _mix(), 1, 32768)
    shapes = {(len(r.prompt_ids), r.max_tokens) for r in reqs}
    buckets = {max(16, 1 << (n - 1).bit_length()) for n, _ in shapes}
    assert buckets == {32, 64, 128, 256, 512, 1024}
    for span_pages in (4, 8, 16):       # lone requests: width = tokens - 1
        widths = {m - 1 for n, m in shapes
                  if -(-(n + m) // 64) <= span_pages
                  and -(-(n + m) // 64) > span_pages // 2}
        assert set(range(1, 9)) <= widths, (span_pages, widths)
    assert all(not r.counted for r in reqs)


def test_the_cycle_depends_on_neither_window_nor_rate():
    """Another --seconds or another rate of the sweep meets the same
    requests in the same order, with the gaps scaled by the rate."""
    def walk(rate, window):
        reqs = traffic.open_loop_schedule(_mix(), rate, window, 0.0, 0.0, 5,
                                          32768)
        return [(r.index, len(r.prompt_ids), r.max_tokens,
                 round(r.due_s * rate, 6)) for r in reqs if r.counted]

    a, b, c = walk(5.6, 40.0), walk(5.6, 20.0), walk(2.8, 40.0)
    assert len(a) == 224 and 100 <= len(b) <= 124
    assert a[:len(b)] == b and a[:len(c)] == c
    # more than one turn: the cycle comes round again
    longer = walk(5.6, 60.0)
    assert longer[224][1:3] == longer[0][1:3]


def test_arrivals_are_the_schedule_without_its_token_ids():
    """What replays a cycle's every entry in no time is what a run meets."""
    mix = dict(_mix(), shared_prefix={"tokens": 24, "count": 3})
    for seed in (5, 2**31 + 11, 4100000012):
        reqs = traffic.open_loop_schedule(mix, 5.6, 40.0, 6.0, 20.0, seed,
                                          32768)
        rows = traffic.arrivals(mix, 5.6, 40.0, 6.0, 20.0,
                                traffic.cycle_entry(mix, seed))
        assert [(r.index, r.due_s, r.counted, r.prompt_tokens, r.max_tokens)
                for r in reqs] == [
            (a.index, a.due_s, a.counted, a.prompt_tokens, a.max_tokens)
            for a in rows]
        assert all(len(r.prompt_ids) == r.prompt_tokens for r in reqs)


# ------------------------------------------------------ the traced stretch
def _due(*rows):
    """Hand-made arrivals: (due_s, prompt tokens), all counted."""
    return [traffic.Arrival(index=i, due_s=due, counted=True,
                            prompt_tokens=tokens, max_tokens=8)
            for i, (due, tokens) in enumerate(rows)]


def test_the_stretch_opens_a_second_before_the_densest_arrivals():
    schedule = _due((5.0, 100), (12.0, 100), (12.5, 100), (13.9, 100),
                    (20.0, 100), (21.0, 100), (30.0, 100))
    # from 11.0 the middle [12.0, 14.0] holds three; from 19.0 two
    assert traffic.traced_stretch(schedule, 40.0, 4.0) == 11.0
    assert [r.index for r in traffic.due_in_middle(schedule, 11.0, 4.0)] \
        == [1, 2, 3]
    # the request a stretch is opened for is in its own middle, though
    # (due - 1.0) + 1.0 need not be due to the last bit
    for due in (3.1, 7.3, 12.7, 0.1 + 0.2 + 9.0):
        lone = _due((due, 10))
        assert traffic.due_in_middle(
            lone, traffic.traced_stretch(lone, 40.0, 4.0), 4.0) == lone


def test_a_tie_goes_to_the_prompt_tokens_then_to_the_earliest():
    two_each = _due((6.0, 100), (7.0, 100), (16.0, 100), (17.0, 900),
                    (26.0, 100), (27.0, 900))
    assert traffic.traced_stretch(two_each, 40.0, 4.0) == 15.0
    alike = _due((6.0, 100), (7.0, 100), (16.0, 100), (17.0, 100))
    assert traffic.traced_stretch(alike, 40.0, 4.0) == 5.0


def test_the_stretch_keeps_two_seconds_clear_of_the_windows_edges():
    # the crowds at both ends are out of reach: 2.9 - 1 is under 2, and a
    # stretch opened for 36.1 would close at 39.1, past 40 - 2
    schedule = _due((2.5, 1), (2.7, 1), (2.9, 1), (3.0, 1), (10.0, 1),
                    (36.1, 1), (36.2, 1), (36.3, 1), (36.4, 1))
    assert traffic.traced_stretch(schedule, 40.0, 4.0) == 2.0
    # both edges belong to the window's middle: 2.0 above, 34 + 4 = 38 here
    assert traffic.traced_stretch(_due((35.0, 1), (35.5, 1)), 40.0, 4.0) \
        == 34.0
    # a request that is not counted opens no stretch, but counts inside one
    lead = [traffic.Arrival(0, 4.0, False, 1, 8)] + _due((4.5, 1))
    assert traffic.traced_stretch(lead, 40.0, 4.0) == 3.5
    assert len(traffic.due_in_middle(lead, 3.5, 4.0)) == 1   # 4.0 < 4.5


def test_a_short_window_opens_a_quarter_in_as_before():
    schedule = _due((1.0, 1), (2.0, 1), (3.0, 1), (4.0, 1))
    # a rehearsal of 5 s: the stretch is 1.5 s, under the three it takes
    assert traffic.traced_stretch(schedule, 5.0, 1.5) == 1.25
    # ten seconds with a stretch of 3: 2 <= a and a + 3 <= 8 has room ...
    assert traffic.traced_stretch(schedule, 10.0, 3.0) == 2.0
    # ... and seven seconds with one of 4 has none: 2 + 4 > 7 - 2
    assert traffic.traced_stretch(schedule, 7.0, 4.0) == 1.75
    assert traffic.traced_stretch([], 40.0, 4.0) == 10.0


def _serve_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = []
    for listed in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads",
                               listed["name"] + ".json")) as f:
            cell = json.load(f)
        if cell["kind"] == "serve":
            cells.append(pytest.param(cell, bench["run_seconds"],
                                      id=cell["name"]))
    return cells


@pytest.mark.parametrize("cell, run_seconds", _serve_cells())
def test_every_entry_of_a_cells_cycle_traces_a_stretch_with_work(
        cell, run_seconds):
    """Whatever ``--seed``, the stretch a traced run of a cell of
    ``BENCHMARK.json`` reads has at least 3 requests due in its middle
    two seconds, and the cycle's entries share a few places: from the
    cycle's gaps and sizes alone (no token id is drawn)."""
    mix = _mix(cell["traffic"])
    for_s = min(4.0, 0.3 * run_seconds)
    places = Counter()
    for start in range(int(mix["cycle_requests"])):
        rows = traffic.arrivals(
            mix, cell["rate_rps"], run_seconds, cell.get("lead_in_s", 5),
            cell.get("drain_s", 20), start)
        opens_s = traffic.traced_stretch(rows, run_seconds, for_s)
        inside = traffic.due_in_middle(rows, opens_s, for_s)
        assert len(inside) >= 3, (start, opens_s)
        assert 2.0 <= opens_s <= run_seconds - 2.0 - for_s
        places[(start + inside[0].index) % int(mix["cycle_requests"])] += 1
    assert len(places) <= 6, places
