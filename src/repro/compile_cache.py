"""Where JAX and the TPU library write: the persistent compilation cache
and libtpu's logs.

Set once at process entry (``chip_smoke.py`` and the launchers'
``__main__`` blocks), never at import: tests import the launchers and
call their ``main()``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed directory inside the checkout (gitignored).  The path is part of
#: the cache key, so it never moves: no temp names, process ids or times.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
#: libtpu writes its logs under /tmp/tpu_logs unless ``TPU_LOG_DIR`` says
#: otherwise; "disabled" turns them off
TPU_LOG_DEFAULT = "disabled"


def place_compile_cache() -> None:
    """Follow ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it
    itself); otherwise cache compiled programs under `CACHE_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def place_tpu_logs() -> None:
    """Follow ``TPU_LOG_DIR`` where it is set; otherwise keep libtpu from
    writing logs outside the checkout.  Must run before libtpu loads,
    i.e. before the first device query or TPU topology description."""
    os.environ.setdefault("TPU_LOG_DIR", TPU_LOG_DEFAULT)
