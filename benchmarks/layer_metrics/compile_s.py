"""Compiler seconds during set-up (jax.monitoring backend-compile events)."""
LAYER, UNIT, MOVES = "entry (cli, process start)", "s", "setup_s"


def read(trace, counters, cell):
    return counters.get("compile_s")
