"""goodput `data_wait` seconds accrued in the window over the window's wall time."""
LAYER, UNIT, MOVES = "trainer (trainer/trainer.py)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    if "data_wait_s" not in counters:
        return None
    return 100.0 * counters["data_wait_s"] / counters["window_s"]
