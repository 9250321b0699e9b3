"""Shared NumPy/jax backend plumbing for the batched engines.

The vectorized layers — the grid-evaluation solvers (``core.grid_eval``) and
the trace-driven execution engine (``core.simulate``) — share a backend
vocabulary resolved here so every entry point behaves identically:

 * ``"numpy"``  — the reference implementation, always available.
 * ``"jax"``    — jit + vmap programs, runs on-accelerator.

Selection rules:

 * ``check_backend``     — validate an explicit backend name.
 * ``jax_available``     — cached import probe; monkeypatchable in tests.
 * ``resolve_backend``   — map a request (``None`` / a backend name) to the
   backend that will actually run. ``None`` defers to the
   ``FULCRUM_ENGINE_BACKEND`` environment variable and **defaults to NumPy**;
   an env-var request for jax degrades to numpy when jax is missing (the
   default path must never fail), while an *explicit* ``backend="jax"``
   argument raises, so a caller that asked for the accelerator tier is told
   it is absent. An unknown name raises ``ValueError`` either way.
 * ``require_jax``       — the lazy jax import used by the jax kernels, with
   one shared error message.

The reference-backend invariant (NumPy results are authoritative; jax is
cross-checked against them) is documented in ``docs/exactness.md``.

This module also names the **host-dispatch counters** (kept in
``repro.obs``): every compiled-program launch (a host->accelerator
synchronization point) is recorded by the layer that made it —
``"solver"`` for the grid-solver kernels (``core.grid_eval``), ``"engine"``
for the max-plus scan runners (``core.simulate``, one per lane chunk),
``"fused"`` for the fused fleet-window program (``core.fused_window``).
``dispatch_count()`` lets benchmarks report dispatches-per-window as a
tracked number (the fused window's whole point is driving it to 1) and lets
tests pin it.
"""
from __future__ import annotations

import os
from typing import Callable, Optional

from repro import obs

#: Environment variable consulted when no explicit backend is requested.
ENGINE_BACKEND_ENV = "FULCRUM_ENGINE_BACKEND"

#: Backend tiers, accelerator first; resolve_backend degrades rightward.
BACKEND_TIERS = ("jax", "numpy")

_JAX_OK: Optional[bool] = None      # memoized import probe (tests patch)

_JAX_MISSING_MSG = ("backend='jax' requires jax; "
                    "use the default NumPy backend")


def _installed(module: str) -> bool:
    """True when ``module`` imports; False only when it is not installed.
    Any other failure while importing it (a broken install, an API that
    moved) propagates as itself rather than reading as "absent"."""
    try:
        __import__(module)
    except ModuleNotFoundError as e:
        if e.name is not None and (module + ".").startswith(e.name + "."):
            return False
        raise
    return True


def jax_available() -> bool:
    """True when jax imports; probed once and memoized."""
    global _JAX_OK
    if _JAX_OK is None:
        _JAX_OK = _installed("jax")
    return _JAX_OK


def check_backend(backend: str) -> None:
    """Validate an explicit backend name against ``BACKEND_TIERS``."""
    if backend not in BACKEND_TIERS:
        raise ValueError(f"unknown backend {backend!r}; use one of "
                         f"{'/'.join(repr(a) for a in BACKEND_TIERS)}")


def resolve_backend(backend: Optional[str] = None,
                    env: str = ENGINE_BACKEND_ENV) -> str:
    """Resolve a backend request to the backend that will run.

    ``None`` reads ``env`` (default ``"numpy"``, the bitwise/exact reference)
    and degrades an env-level ``"jax"`` request to numpy when jax is
    unavailable. An explicit ``"jax"`` argument raises ``RuntimeError``
    instead of degrading; an unknown name raises ``ValueError``.
    """
    defaulted = backend is None
    if defaulted:
        backend = os.environ.get(env, "").strip().lower() or "numpy"
    check_backend(backend)
    if backend == "jax" and not jax_available():
        if defaulted:
            return "numpy"
        raise RuntimeError(_JAX_MISSING_MSG)
    return backend


def record_dispatch(kind: str) -> None:
    """Record one compiled-program launch of the given layer."""
    obs.count("dispatch." + kind)


def with_program(run: Callable, program: Callable) -> Callable:
    """Attach the jitted ``program`` behind the host wrapper ``run`` as
    ``run.jitted``, so ahead-of-time compiles can lower it directly."""
    run.jitted = program
    return run


def dispatch_count(kind: Optional[str] = None) -> int:
    """Compiled-program launches since import: one layer's count, or the
    total across layers (``kind=None``) — the number a serving loop's
    dispatches-per-window is measured from."""
    return obs.counted("dispatch" if kind is None else "dispatch." + kind)


def require_jax():
    """Import (jax, jax.numpy, enable_x64), raising the shared message when
    jax is not installed. The jax kernel caches build through this;
    ``enable_x64()`` is a context manager that turns float64 on."""
    if not jax_available():
        raise RuntimeError(_JAX_MISSING_MSG)
    import jax
    import jax.numpy as jnp
    return jax, jnp, jax.enable_x64
