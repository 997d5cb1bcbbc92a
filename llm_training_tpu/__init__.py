"""llm-training-tpu: a TPU-native (JAX/XLA/Pallas/pjit) LLM training framework.

A from-scratch re-design of the capabilities of cchou0519/LLM-Training
(full-parameter pre-training / instruction tuning / DPO / ORPO of Llama- and
Phi-3-family models) built TPU-first:

- single-program SPMD over a `jax.sharding.Mesh` (data / fsdp / tensor / sequence axes)
- GSPMD-sharded parameters (ZeRO-3 semantics), tensor + sequence parallelism via
  logical-axis sharding rules, ring attention for long context
- Pallas TPU kernels for the hot ops (flash attention with segment-id packing,
  fused-linear-cross-entropy) with XLA fallbacks
- optax optimizers (fp32 master state over bf16 compute), orbax checkpoints,
  HF checkpoint round-tripping
"""

import time as _time

# the package's first clock reading (`time.perf_counter()`, the tracer's
# clock): what the start-up timeline's `setup/ready_s` counts from
PROCESS_START = _time.perf_counter()

__version__ = "0.1.0"
