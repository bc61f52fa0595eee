"""Persistent XLA compilation cache for the entry points.

A cold start on the chip compiles every step program, which can take
minutes for a full-width model; the persistent cache lets the next process
on the same machine skip that. Entries are found again only if every run
looks in the same place, so the directory is fixed, never a temp, pid or
time-stamped name: either the one the deployment names in
`JAX_COMPILATION_CACHE_DIR` (JAX reads that variable itself) or
`.jax_cache` inside the checkout.

Call `enable()` at the start of an entry point's `main`, never on import:
tests and library users keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Turn the persistent cache on."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
