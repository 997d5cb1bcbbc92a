"""Decoding rows over max_batch, mean over the window's decode steps (count)."""
LAYER, UNIT, MOVES = "serving (serve/engine.py, serve/scheduler.py)", "%", "serve_tok_s"


def read(trace, counters, cell):
    if not counters.get("decode_steps"):
        return None
    return 100.0 * counters["decode_rows"] / (counters["decode_steps"] * counters["max_batch"])
