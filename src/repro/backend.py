"""Backend-dependent decisions, made in one place.

* **Kernel mode.**  Every Pallas entry point takes ``interpret=None`` and
  asks :func:`interpret_kernels`: the CPU backend (the test suite) runs the
  kernel bodies in the Pallas interpreter, the TPU backend compiles them to
  Mosaic.  Any other backend is an error — there is no silent fallback.
* **Compile cache.**  :func:`setup_compile_cache` points JAX's persistent
  compilation cache at ``$JAX_COMPILATION_CACHE_DIR`` when that is set and
  at ``.jax_cache/`` in the checkout root otherwise.  Entry points call it
  at start-up; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def interpret_kernels() -> bool:
    """Whether Pallas kernels run interpreted on the default backend."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel mode for backend {backend!r}: kernels compile on "
        "'tpu' and run interpreted on 'cpu'")


def resolve_interpret(interpret) -> bool:
    """An explicit ``interpret`` wins; ``None`` defers to the backend."""
    return interpret_kernels() if interpret is None else bool(interpret)


def setup_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache lives at a fixed
    path in the checkout, so a later process finds what an earlier one
    compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
