"""Platform choices the rest of the repo makes in one place.

* ``make_mesh(shape, axes)`` — ``jax.make_mesh`` with ``Auto`` axis types
  (JAX's default is ``Explicit``, which the shard_map engines do not use).
* ``resolve_backend`` / ``pallas_executor`` — the one place that decides how
  the fused migration kernels execute on this host (DESIGN.md §9): native
  Mosaic on TPU, the bit-exact pure-jax oracle on CPU, or the Pallas
  interpreter when a test forces it.
* ``enable_compile_cache`` — JAX's persistent compilation cache for entry
  points (``chip_smoke.py``, the benchmarks); never called on import.
* ``request_host_devices`` — fake CPU devices for dry runs and
  multi-device rehearsals, asked for only where JAX may run on the CPU.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Sequence, Tuple

import jax

_CHECKOUT = Path(__file__).resolve().parents[2]


def make_mesh(shape: Tuple[int, ...], axes: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def request_host_devices(count: int) -> bool:
    """Ask the CPU backend for ``count`` devices by appending
    ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``.

    The flag sizes only JAX's host platform, and JAX picks its platform
    only when it initialises (falling back to the CPU where no accelerator
    answers), so the flag goes in wherever the CPU may be picked: always,
    unless a count is already set or ``JAX_PLATFORMS`` leaves the CPU out.
    Call it before JAX initialises its backends (the flag is read once,
    then), and check the platform JAX picked afterwards. Returns whether
    the flag is in place.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return True
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        return False
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={count}".strip())
    return True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so a
    path that moved between runs would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Compute-backend selection for the fused migration kernels (DESIGN.md §9)
# ---------------------------------------------------------------------------

_BACKENDS = ("ref", "pallas")
_EXECUTORS = ("native", "interpret", "jax")


def resolve_backend(backend: str = "auto") -> str:
    """Resolve ``SystemConfig.compute.backend`` to ``"ref"`` or ``"pallas"``.

    ``"auto"`` (the default, overridable via ``REPRO_COMPUTE_BACKEND``)
    selects the fused ``"pallas"`` path: it has an executor on every
    platform (see :func:`pallas_executor`) and is bit-identical to the
    reference path, so there is never a correctness reason to avoid it.
    ``"ref"`` keeps the unfused op-by-op scoring pipeline — the oracle the
    parity suite and the kernel benchmark compare against.
    """
    if backend == "auto":
        backend = os.environ.get("REPRO_COMPUTE_BACKEND", "pallas")
        if backend == "auto":                # env var may restate the default
            backend = "pallas"
    if backend not in _BACKENDS:
        raise ValueError(f"unknown compute backend {backend!r}; "
                         f"valid: {('auto',) + _BACKENDS}")
    return backend


def pallas_executor() -> str:
    """How the fused kernels execute on this host.

    * ``"native"``    — Mosaic-compiled Pallas kernels over BSR tiles
                        (TPU; the MXU path DESIGN.md §9 describes).
    * ``"interpret"`` — the same Pallas kernels under ``interpret=True``
                        (bit-faithful to the kernel body; used by the CPU
                        parity CI via ``REPRO_PALLAS_EXECUTOR=interpret``).
    * ``"jax"``       — the fused pure-jax oracle from ``kernels/ref.py``
                        (CPU default: interpreting per-tile Python inside a
                        streaming loop is a debugger, not a runtime).

    All three produce bit-identical partition assignments; the parity suite
    (``tests/test_migration_kernels.py``) holds that as a property.
    """
    executor = os.environ.get("REPRO_PALLAS_EXECUTOR")
    if executor is not None:
        if executor not in _EXECUTORS:
            raise ValueError(f"unknown REPRO_PALLAS_EXECUTOR {executor!r}; "
                             f"valid: {_EXECUTORS}")
        return executor
    return "native" if jax.default_backend() == "tpu" else "jax"
