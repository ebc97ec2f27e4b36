"""Process start-up shared by every entry point: where the persistent
compile cache lives, and what the process says about the device it got.

One process owns a chip (a second one that touches JAX on the same host
fails or hangs), so everything here runs in the process that will serve,
once, before its first JAX computation.
"""

from __future__ import annotations

import os
from pathlib import Path

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed by the checkout's location, never by a temp name, pid or time: a
# cache that moves between runs never hits
CHECKOUT_COMPILE_CACHE = Path(__file__).resolve().parent.parent / ".jax_cache"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory in
    effect. Where JAX_COMPILATION_CACHE_DIR is set the caller chose the
    place and JAX reads the variable itself — no directory is set in code.
    Otherwise the cache goes to `<checkout>/.jax_cache`.

    Either way every program is kept, not only those that took JAX's
    default second or more to compile: a node compiles one small program
    per (kernel, batch width, k bucket), and each one missing from the
    cache is a slow first query after a restart."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT_COMPILE_CACHE))
    return str(CHECKOUT_COMPILE_CACHE)


def startup_report(compile_cache_dir: str) -> dict:
    """What a node prints once when it comes up: the device JAX gave it
    (first touch of the backend — with JAX_PLATFORMS=tpu and no chip this
    raises, and the node does not start), whether the native library
    loaded, and where compiled programs are kept."""
    import jax

    from opensearch_tpu import native

    devices = jax.devices()
    return {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
        "native_available": native.native_available(),
        "compile_cache_dir": compile_cache_dir,
    }
