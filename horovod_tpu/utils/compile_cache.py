"""Where JAX's persistent compilation cache lives.

One rule, shared by every entry point that compiles for the chip
(``benchmark/run.py``, ``chip_smoke.py``, the serve worker): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and no
directory is set in code; otherwise the cache is ``<checkout>/.jax_cache``
(git-ignored).  The path is part of the cache key, so it is never a
temporary name, a pid or a time.

The same call arms the program's set-up log
(``horovod_tpu.obs.profile.compile_log()``): what was traced, lowered
and compiled, from when to when, whether the cache had it and what its
load took.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional, Tuple

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def resolve_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> Tuple[str, bool]:
    """``(directory, set_in_code)``: the variable's directory, which JAX
    reads itself, or the checkout's, which code has to set."""
    placed = environ.get(CACHE_ENV)
    if placed:
        return placed, False
    return DEFAULT_CACHE_DIR, True


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on for every compile, however short or
    small, and return the directory it uses.  Call before the first
    compile of the process.

    A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, a
    ``--cpu`` dry run) is left alone and gets None: its compiles take
    seconds, and XLA:CPU logs a machine-feature mismatch on every load
    of an entry it wrote itself."""
    import jax  # noqa: PLC0415 — importers of utils must stay off jax

    from ..obs.profile import install_compile_listener  # noqa: PLC0415

    # Every entry point calls this before its first compile, so this is
    # where the set-up log (obs/profile.py: program, phase, interval,
    # cache hit or miss and the load's seconds) starts listening and
    # takes its clock pair — on the CPU too, where only the cache
    # itself is left alone.
    install_compile_listener()
    if jax.config.jax_platforms == "cpu":
        return None
    cache_dir, set_in_code = resolve_cache_dir()
    if set_in_code:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
