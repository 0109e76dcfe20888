"""Engine: mean share of decode slots that hold a request
(``stats()["running"] / max_num_seqs``), sampled at 10 Hz over the
window."""

NAME, UNIT, SOURCE = "slot_occupancy", "%", "program_counter"
LAYER, MOVES, KINDS = "LLM replica and engine", "serve_tok_s", ("serve",)


def compute(run):
    inside = [s["running"] for s in run["engine"]["occupancy"]
              if 0 <= s["t"] <= run["window_s"]]
    if not inside:
        return None
    return 100.0 * sum(inside) / len(inside) / run["engine"]["max_num_seqs"]
