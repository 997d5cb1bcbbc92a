"""One persistent XLA compile cache, placed from outside.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and the
program names no other directory. Where it is not, the cache lives at ONE
fixed path inside the checkout (`<repo>/.jax_cache`, git-ignored). The
directory is part of what a cache entry is keyed on, so a path built from a
temporary name, a pid or the time would never hit: every process of the
repo — `fit`, `serve`, `chip_smoke.py`'s phase children — must
land in the same place for a second run to compile nothing.

An entry is also keyed on the program's metadata (`jax.named_scope`s, flax
module names, source lines), which JAX leaves out by default: a program read
back from an entry that an older checkout wrote would carry THAT checkout's
op names, and a device profile would put its time under scopes the running
code no longer has, or not under the ones it has (seen on the chip, PR 25:
`moe_*` and `sample` missing from every op of a cached decode step). The
price is that an edit to a traced source file compiles again.

Importing this module does not import jax (chip_smoke.py's parent stays
off the chip); `configure_compile_cache` does.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where this process's compiles are cached: the environment's choice,
    else the fixed in-checkout path."""
    return os.environ.get(ENV_CACHE_DIR) or str(DEFAULT_CACHE_DIR)


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at `compile_cache_dir()`; returns it.
    With the variable set nothing is set in code — JAX already read it.
    JAX's own thresholds stay (programs that compile in under a second are
    not written). Keys hold the programs' metadata (module docstring). The
    first thing a process of the repo does with jax, so the program starts
    to hear jax's compile events here (docs/observability.md#tracing)."""
    import jax

    from llm_training_tpu.telemetry.profiling import install_compile_listener

    install_compile_listener()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return compile_cache_dir()
