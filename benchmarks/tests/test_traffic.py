import json
import os

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
