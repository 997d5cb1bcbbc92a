"""Pallas TPU kernels for the hot ops.

TPU-native replacement for the reference's external native kernels
(flash-attn CUDA, liger-kernel Triton — see SURVEY.md §2.9). Each kernel has
an XLA fallback in `llm_training_tpu.ops`; dispatch is via the `impl=`
arguments on the op entry points.
"""


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel call runs in the interpreter. Off-TPU the
    interpreter is the only way a kernel runs at all (tier-1 tests); on a
    TPU a kernel always compiles — `interpret=True` there raises, so no
    caller can quietly take the chip's kernels off the chip. An explicit
    `False` is honoured anywhere (compiling for a described device)."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: Pallas kernels compile on the "
            "chip; the interpreter is the off-TPU test path only"
        )
    return bool(interpret)
