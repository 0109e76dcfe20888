"""Engine: mean number of fused decode steps in a round
(``LLMEngine._burst_width``: the least any active slot still wants, at
most ``decode_burst``), over the rounds of the window. Narrow rounds mean
a host round trip every few tokens."""

NAME, UNIT, SOURCE = "decode_burst_width", "steps", "program_counter"
LAYER, MOVES, KINDS = "LLM replica and engine", "tpot_p95_ms", ("serve",)


def compute(run):
    widths = [r["width"] for r in run["engine"]["rounds"]
              if 0 <= r["t"] <= run["window_s"]]
    return sum(widths) / len(widths) if widths else None
