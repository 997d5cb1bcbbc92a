"""Share of the window's engine steps that ran a prefill chunk (count)."""
LAYER, UNIT, MOVES = "serving (serve/engine.py, serve/scheduler.py)", "%", "itl_p95_ms"


def read(trace, counters, cell):
    if not counters.get("steps") or "prefill_steps" not in counters:
        return None
    return 100.0 * counters["prefill_steps"] / counters["steps"]
