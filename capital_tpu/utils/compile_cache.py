"""Where JAX's persistent compilation cache lives — placed from outside.

The entry points that run on the chip (chip_smoke.py and the
``python -m capital_tpu.{bench,serve,autotune}`` CLIs) call `enable()`
once, before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set → that directory, and no other;
* unset → the fixed ``<checkout>/.jax_cache`` (gitignored).  The path is
  part of what a later run must find, so it never moves with the cwd.

The minimum compile time drops to 0 so the one-to-few-second Mosaic
kernel compiles are cached along with the large factor programs.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable() -> str:
    """Turn the persistent cache on (see module docstring); returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
