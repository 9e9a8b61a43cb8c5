"""What this process runs on, asked in one place.

Every "is this a TPU" decision (Pallas kernels vs the XLA bodies, device
tree growth vs the host loop, device GOSS) goes through `on_tpu()`, and
every Pallas call site takes its `interpret` flag from
`pallas_interpret()`. Neither guesses: a backend that fails to initialise
raises out of `jax.devices()` and the caller sees it — nothing here turns
that into "use the CPU path" — and a kernel is interpreted only when the
tests ask for it by name.
"""
from __future__ import annotations

import os

import jax

COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def on_tpu() -> bool:
    """True when the default device is a TPU."""
    return jax.devices()[0].platform == "tpu"


def pallas_interpret() -> bool:
    """LGBM_TPU_PALLAS_INTERPRET=1 runs the Pallas kernels in interpret
    mode (how the CPU tests cover the kernel bodies). Never inferred from
    the backend: off a TPU a compiled kernel fails to lower, loudly."""
    return os.environ.get("LGBM_TPU_PALLAS_INTERPRET", "").lower() in (
        "1", "true", "on")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place, once, at
    package import. Where JAX_COMPILATION_CACHE_DIR is set, or the
    application already gave JAX a directory (`jax.config.update`) before
    importing this package, that choice stands and nothing is set here;
    otherwise it is `<checkout>/.jax_cache`. The path is part of every
    cache key, so it is never built from a temp name, a pid or a time.
    Returns the directory in force."""
    if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # every operation's name stack and source line is part of the cache key.
    # JAX leaves metadata out of it, so an executable compiled from ANOTHER
    # version of this source with the same arithmetic would be loaded with
    # that version's names, and a device trace would show `lgbm.` scopes
    # (utils/timer.py) this source no longer has, or none. One key for every
    # run: a profiled run reads the executable the timed runs ran, and the
    # first run after an edit that moves a traced line compiles afresh.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
