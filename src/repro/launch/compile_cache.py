"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache when it is set, and no
    other directory is used.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, because the path is part of what a later run must match to
    find an entry.  Call it before the first compilation of the process;
    JAX fixes the cache's directory when it first compiles."""
    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(path))
    # A profile names the step's phases by the scopes in the executable's
    # metadata, and by default the key leaves metadata out: an entry written
    # by a tree with other scopes would be found and run with that tree's
    # names. So the key covers the metadata, and the checkout's own path is
    # taken out of the source file names, so that a moved checkout still hits.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT}{os.sep}"))
    return path
