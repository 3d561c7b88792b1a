"""Where JAX's persistent compilation cache lives — one policy, one place.

Every entry point that compiles (``train.py``, ``python -m
shallowspeed_tpu.serving``, ``bench.py``, ``chip_smoke.py``,
``tests/conftest.py``) calls ``enable_compile_cache()`` before its first
compile, so a second process on the same machine loads executables instead of
rebuilding them. The directory is part of the cache key, so it has to be the
same path every time: ``<base>/<tag>``, where ``<base>`` is
``JAX_COMPILATION_CACHE_DIR`` when the caller set it, otherwise ``.jax_cache``
beside the package, resolved from this file's own location and never from the
working directory, a temporary name, a pid or the clock.

``<tag>`` is ``observability.scopes.CACHE_TAG``, a constant derived from the
names in ``scopes.SCOPES``. JAX computes the cache key AFTER stripping debug
metadata, and ``jax.named_scope`` names are metadata: a tree whose programs
differ only in their scopes would load another tree's executables from a
shared directory, and every scope would silently vanish from
``Compiled.as_text()`` and the profiler. The tag keeps trees with different
scope names apart and is the same for every process of one tree. It cannot
see a scope that MOVED under an unchanged name: rename the scope or bump
``scopes._SALT`` then. (``jax_compilation_cache_include_metadata_in_key`` is
not the cure: it keys on file paths and line numbers, so every checkout and
every edited line would compile cold.)

This is JAX's own store, and the only executable store the repository has.

The same call registers the package's one set of compile listeners
(``observability.spans.listen_to_compiles``): every entry point makes it
before its first compile, so the span log holds each trace, lowering, backend
compile and cache load of the process, by phase and by function.
"""

import os
from pathlib import Path

import jax

from shallowspeed_tpu.observability.scopes import CACHE_TAG
from shallowspeed_tpu.observability.spans import listen_to_compiles

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in effect.

    Thresholds: cache every program, however small or quick. A training or
    serving process compiles dozens of sub-second helper programs next to
    the one big epoch program; at the default thresholds (1 s, and a
    minimum entry size) they would be rebuilt by every process.
    """
    listen_to_compiles()
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(Path(base) / CACHE_TAG))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
