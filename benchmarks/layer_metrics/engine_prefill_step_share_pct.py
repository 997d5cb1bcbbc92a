"""Share of the traced engine steps that ran a prefill chunk, from the
engine's own counts: the closing args of its `serve/engine_step` spans. Logs
the prompt tokens a chunk took (`prefill_chunk_device_ms` is per chunk)."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "serving (serve/engine.py, serve/scheduler.py)", "%", "itl_p95_ms"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    if (older := span_reduce.older_program(spans)) is not None:
        return older
    mine = span_reduce.step_counts(spans)
    if not mine["steps"]:
        return None
    chunks = mine["prefill_steps"]
    common.log(
        f"{mine['prefill_tokens']} prompt tokens in {chunks} chunks of {mine['steps']} steps"
        + (f": {mine['prefill_tokens'] / chunks:.1f} a chunk" if chunks else "")
    )
    return 100.0 * chunks / mine["steps"]
