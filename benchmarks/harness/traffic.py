"""The one traffic generator: a mix's data file in, a schedule out.

A mix (``benchmarks/traffic/<name>.json``) states distributions, not
requests. The generator turns it into one fixed *cycle* of
``cycle_requests`` request sizes and inter-arrival gaps, drawn as
stratified quantiles of the stated distributions and put in an order
fixed by the mix's own ``mix_seed``. The cycle does not depend on the
rate (which only scales the gaps), on ``--seconds`` or on ``--seed``.
The run's ``--seed`` decides where in the cycle the window opens and
draws every token id. So two seeds do the same work from another
starting point, the same seed gives the same inputs to the byte, and a
tail read from a run is the tail of this one realization of the mix,
not of the distributions behind it.

Nothing here imports jax or numpy, and nothing reads a clock.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Sequence

# a request due within this many seconds of the window's edge is put on
# the side the cycle's arithmetic means: sums of floats end 1e-13 off
EDGE_S = 1e-9


@dataclass(frozen=True)
class Request:
    index: int            # position in the schedule; 0 = first of the window
    due_s: float          # seconds after the window opens (lead-in: below 0)
    counted: bool         # due inside the window
    prompt_ids: tuple
    max_tokens: int
    temperature: float

    @property
    def prompt_tokens(self) -> int:
        return len(self.prompt_ids)


@dataclass(frozen=True)
class Arrival:
    """A request of the schedule without its token ids: what the cycle's
    gaps and sizes alone say of it."""
    index: int
    due_s: float
    counted: bool
    prompt_tokens: int    # the shared prefix counted in
    max_tokens: int


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def stratified_sizes(spec: dict, n: int) -> List[int]:
    """``n`` whole numbers whose histogram follows ``spec``, a lognormal
    distribution (median, sigma of the underlying normal): its
    (i + 0.5) / n quantiles, clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    normal = NormalDist()
    lo, hi = int(spec["min"]), int(spec["max"])
    return [max(lo, min(hi, int(round(math.exp(
        mu + sigma * normal.inv_cdf(u)))))) for u in _quantiles(n)]


def stratified_gaps(arrivals: str, n: int, rng: random.Random,
                    cv: float = 1.0) -> List[float]:
    """``n`` inter-arrival gaps with mean 1 exactly, so that one turn of
    the cycle at ``rate`` requests a second lasts ``n / rate`` seconds
    whatever the order. ``poisson``: quantiles of the exponential
    distribution. ``gamma``: draws with the coefficient of variation
    ``cv`` (bursts for cv > 1), from ``rng`` (the mix's own seed, so
    still a fixed set)."""
    if arrivals == "poisson":
        gaps = [-math.log(1.0 - u) for u in _quantiles(n)]
    elif arrivals == "gamma":
        shape = 1.0 / (cv * cv)
        gaps = [rng.gammavariate(shape, 1.0 / shape) for _ in range(n)]
    else:
        raise ValueError(f"unknown arrival process {arrivals!r}")
    scale = n / sum(gaps)
    return [g * scale for g in gaps]


def request_cycle(mix: dict) -> List[tuple]:
    """The mix's fixed cycle: ``cycle_requests`` triples of (gap before
    the request at one request a second, prompt tokens, output tokens),
    in the order ``mix_seed`` gives. The same for every run, every rate
    and every window length."""
    n = int(mix["cycle_requests"])
    rng = random.Random(int(mix["mix_seed"]))
    prompts = stratified_sizes(mix["prompt_tokens"], n)
    outputs = stratified_sizes(mix["output_tokens"], n)
    gaps = stratified_gaps(mix.get("arrivals", "poisson"), n, rng,
                           float(mix.get("arrival_cv", 1.0)))
    for column in (prompts, outputs, gaps):
        rng.shuffle(column)
    return list(zip(gaps, prompts, outputs))


def _token_ids(rng: random.Random, n: int, vocab: int) -> tuple:
    return tuple(rng.randrange(1, vocab) for _ in range(n))


def cycle_entry(mix: dict, seed: int) -> int:
    """Where in the mix's cycle ``seed`` opens the window: the first draw
    of the schedule's generator."""
    return random.Random(seed).randrange(int(mix["cycle_requests"]))


def arrivals(mix: dict, rate_rps: float, window_s: float, lead_in_s: float,
             tail_s: float, start: int) -> List[Arrival]:
    """Due times and sizes of the requests from ``-lead_in_s`` to
    ``window_s + tail_s`` when the window enters the mix's cycle at
    ``start``; ``due_s`` counts from the start of the window. No token
    id is drawn: every entry of a cycle can be replayed in no time."""
    cycle = request_cycle(mix)
    n = len(cycle)
    prefix = mix.get("shared_prefix") or None
    shared = int(prefix["tokens"]) if prefix else 0

    def entry(k: int) -> tuple:
        gap, n_prompt, n_out = cycle[(start + k) % n]
        return gap / rate_rps, n_prompt, n_out

    # request 0 is due one gap after the window opens; walk back from it
    # through the lead-in
    k, due = 0, entry(0)[0]
    while due - entry(k)[0] > -lead_in_s:
        due -= entry(k)[0]
        k -= 1
    out: List[Arrival] = []
    while due <= window_s + tail_s:
        _gap, n_prompt, n_out = entry(k)
        out.append(Arrival(index=k, due_s=due,
                           counted=EDGE_S < due <= window_s + EDGE_S,
                           prompt_tokens=shared + n_prompt,
                           max_tokens=n_out))
        k += 1
        due += entry(k)[0]
    return out


def open_loop_schedule(mix: dict, rate_rps: float, window_s: float,
                       lead_in_s: float, tail_s: float, seed: int,
                       vocab: int) -> List[Request]:
    """Requests due from ``-lead_in_s`` to ``window_s + tail_s``, with
    ``due_s`` counted from the start of the window (so lead-in requests
    have negative due times). The system meets one periodic stream: the
    mix's cycle, entered where ``seed`` says, one turn every
    ``cycle_requests / rate_rps`` seconds. A window of just that length
    holds every request of the cycle once, whatever the seed; the cells
    are sized so (``rate_rps`` x ``run_seconds`` = ``cycle_requests``).
    """
    rng = random.Random(seed)
    start = rng.randrange(int(mix["cycle_requests"]))   # = cycle_entry
    prefix = mix.get("shared_prefix") or None
    prefixes = []
    if prefix:
        prefixes = [_token_ids(rng, int(prefix["tokens"]), vocab)
                    for _ in range(int(prefix["count"]))]
    shared_tokens = len(prefixes[0]) if prefixes else 0
    temperature = float(mix.get("temperature", 0.0))
    requests: List[Request] = []
    for due in arrivals(mix, rate_rps, window_s, lead_in_s, tail_s, start):
        body = _token_ids(rng, due.prompt_tokens - shared_tokens, vocab)
        if prefixes:
            body = prefixes[rng.randrange(len(prefixes))] + body
        requests.append(Request(
            index=due.index, due_s=due.due_s, counted=due.counted,
            prompt_ids=body, max_tokens=due.max_tokens,
            temperature=temperature))
    return requests


# The traced stretch (``--trace 1``): the profiler is given a second to
# start before the requests it is opened for and a second to stop behind
# them, and is kept two seconds clear of the window's edges.
STRETCH_MARGIN_S = 1.0
STRETCH_EDGE_S = 2.0
STRETCH_LEAST_S = 3.0


def due_in_middle(schedule: Sequence, opens_s: float, for_s: float) -> list:
    """The requests due in a stretch's middle: from a second after it
    opens to a second before it closes."""
    lo = opens_s + STRETCH_MARGIN_S - EDGE_S
    hi = opens_s + for_s - STRETCH_MARGIN_S + EDGE_S
    return [r for r in schedule if lo <= r.due_s <= hi]


def traced_stretch(schedule: Sequence, window_s: float,
                   for_s: float) -> float:
    """Seconds after the window opens at which a traced stretch of
    ``for_s`` seconds should open, so that it holds work: a second before
    some counted request is due, no nearer than two seconds to either
    edge of the window, where most requests are due in its middle (ties:
    most prompt tokens due there, then the earliest). The schedule is
    the seed's, so a seed's stretch is fixed before anything runs; the
    cycle's densest arrivals are few, so most seeds read the same
    requests. A stretch under three seconds (a rehearsal) or a window
    without such a place opens a quarter into the window, as every
    stretch did before PR 42."""
    best, best_key = 0.25 * window_s, None
    if for_s < STRETCH_LEAST_S:
        return best
    for request in schedule:
        opens_s = request.due_s - STRETCH_MARGIN_S
        if not (request.counted and STRETCH_EDGE_S <= opens_s
                and opens_s + for_s <= window_s - STRETCH_EDGE_S):
            continue
        inside = due_in_middle(schedule, opens_s, for_s)
        key = (len(inside), sum(r.prompt_tokens for r in inside), -opens_s)
        if best_key is None or key > best_key:
            best, best_key = opens_s, key
    return best


def warmup_requests(engine: dict, mix: dict, seed: int,
                    vocab: int) -> List[Request]:
    """Lone requests, sent one after another, that between them meet
    every program shape the mix can: each prefill bucket (powers of two
    from 16 up to ``max_seq_len``) the mix's prompt range touches, and for
    each page-span bucket a request alone in the batch that asks for
    ``1 + w`` tokens for every burst width ``w`` up to ``decode_burst``
    (alone, the burst's width is what the request still wants). Written
    against what a user can see of the engine (its config), through the
    same HTTP route as the traffic; the mix-driven lead-in after it and
    ``window_compiles`` catch whatever a changed engine compiles besides.
    """
    rng = random.Random(seed ^ 0x5EED)
    page, burst = int(engine["page_size"]), int(engine["decode_burst"])
    max_seq = int(engine["max_seq_len"])
    lo, hi = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
    out_hi = int(mix["output_tokens"]["max"])
    room = max_seq - burst - 2
    lens, bucket = set(), 16
    while bucket // 2 < min(hi, max_seq):
        n = min(bucket, hi, room)
        if n >= lo and n > bucket // 2:
            lens.add(n)
        bucket *= 2
    # one prompt length inside each page-span bucket (4, 8, 16... pages)
    spans, span = [], 4
    while True:
        n = min(span * page, max_seq) - burst - 2
        if n >= lo:
            spans.append(min(n, hi))
        if span * page >= min(max_seq, hi + out_hi):
            break
        span *= 2
    temperature = float(mix.get("temperature", 0.0))
    requests = []
    for n_prompt in sorted(lens):
        requests.append((n_prompt, 1 + burst))
    for n_prompt in spans:
        for width in range(1, burst + 1):
            requests.append((n_prompt, 1 + width))
    return [Request(index=i, due_s=0.0, counted=False,
                    prompt_ids=_token_ids(rng, n, vocab), max_tokens=m,
                    temperature=temperature)
            for i, (n, m) in enumerate(requests)]
