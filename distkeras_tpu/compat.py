"""The repo-wide JAX conventions: which backend a traced program is for
(``backend_is_tpu``), how a kernel-or-reference choice is put on record
(``note_path`` / ``record_paths``), and how ``shard_map`` is called.
"""

from __future__ import annotations

import contextlib
import contextvars
import os

import jax


def backend_is_tpu() -> bool:
    """True when the TRACE-TIME default backend is a TPU — the repo's
    single convention for choosing a Pallas kernel over its XLA
    fallback (``ops.decode_attention``, ``ops.flash_attention``,
    ``ops.moe_kernels``, the decode/prefill paths in
    ``models.decoding``, and ``MoE``'s fused dispatch all route through
    here). The contract this encodes, documented on
    ``models.decoding.generate``: traced programs assume they execute
    on the default backend. Code that must run on a NON-default device
    (e.g. CPU execution inside a TPU-backed process) should wrap the
    call in ``jax.default_device`` so trace-time agrees with run-time,
    rather than expecting per-input device dispatch."""
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point of
    this checkout (``bench.py``, ``chip_smoke.py``, ``tests/``) and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX already uses it and nothing is set here; otherwise the cache is
    ``<repo>/.jax_cache`` — a fixed path inside the checkout, because
    the path is part of the cache key and a directory that moves never
    hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_PATHS = contextvars.ContextVar("kernel_paths", default=None)


def note_path(site: str, path: str) -> None:
    """Called where traced code picks a Pallas kernel or its XLA
    reference (``site``: which op; ``path``: what it picked and, for a
    reference, why). A no-op unless a :func:`record_paths` scope is
    open around the trace."""
    log = _PATHS.get()
    if log is not None:
        log.add(f"{site}={path}")


@contextlib.contextmanager
def record_paths():
    """Collect the :func:`note_path` calls made while a program is
    traced: yields the set they land in. The serving engine opens one
    around each of its jitted programs, so "kernel or reference" is
    read from ``health()`` instead of inferred from the backend."""
    log: set = set()
    token = _PATHS.set(log)
    try:
        yield log
    finally:
        _PATHS.reset(token)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False, **kwargs):
    """``jax.shard_map`` with the varying-manual-axes check OFF unless a
    caller asks for it. The check rejects two things this repo does on
    purpose: a ``pallas_call`` whose ``out_shape`` carries no ``vma``
    (every kernel in ``ops/``) and a custom-VJP backward that returns a
    device-varying cotangent for a replicated parameter (BatchNorm's
    cross-replica statistics). Import ``shard_map`` from here
    everywhere so the decision is made once."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)
