"""Engine: persistent-cache lookups (hits + misses, so every compile or
load of a program) the replica made between the window's opening and its
end, from ``device_plane.compilation_cache_stats()`` sampled at 10 Hz.
Anything but 0 means warm-up missed a shape and the window paid for it."""

NAME, UNIT, SOURCE = "window_compiles", "compiles", "program_counter"
LAYER, MOVES, KINDS = "LLM replica and engine", "ttft_p95_ms", ("serve",)


def compute(run):
    samples = run["engine"]["occupancy"]
    before = [s["compiles"] for s in samples if s["t"] <= 0]
    inside = [s["compiles"] for s in samples if s["t"] <= run["window_s"]]
    if not before or not inside:
        return None
    return float(inside[-1] - before[-1])
