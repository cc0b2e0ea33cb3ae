"""Shared helpers: dtype promotion, grid factorization, user-level warnings.

Reference analog: ``sparse/utils.py`` (store<->cunumeric bridges at utils.py:41-91
disappear on TPU — everything is a jax.Array; the dtype-promotion and grid helpers
at utils.py:120-150 carry over).
"""

from __future__ import annotations

import math
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np


def find_last_user_stacklevel() -> int:
    """Stack level of the first frame outside sparse_tpu, for warnings.warn.

    Reference: ``sparse/utils.py:31-37``.
    """
    import inspect

    level = 1
    for frame, _ in zip(inspect.stack(), range(64)):
        if "sparse_tpu" not in frame.filename:
            break
        level += 1
    return level


def user_warning(msg: str) -> None:
    warnings.warn(msg, stacklevel=find_last_user_stacklevel())


def cast_to_common_type(*arrays):
    """Promote all arrays to a common dtype (reference: utils.py:120-141)."""
    dt = np.result_type(*[a.dtype for a in arrays])
    return tuple(a.astype(dt) for a in arrays)


def common_dtype(*arrays_or_dtypes):
    return np.result_type(
        *[getattr(a, "dtype", a) for a in arrays_or_dtypes]
    )


def factor_int(n: int) -> tuple[int, int]:
    """Factor n into a near-square (x, y) grid, x*y == n.

    Reference: ``sparse/utils.py:144-150`` — used for 2-D processor-grid launches
    (SpGEMM CSRxCSC, cdist, quantum). On TPU this shapes 2-D device meshes.
    """
    x = int(math.isqrt(n))
    while n % x != 0:
        x -= 1
    y = n // x
    return (max(x, y), min(x, y))


def asjnp(a, dtype=None):
    """Convert to a jax array, passing device arrays through untouched."""
    out = jnp.asarray(a)
    if dtype is not None and out.dtype != np.dtype(dtype):
        out = out.astype(dtype)
    return out


def tohost(x) -> np.ndarray:
    """Fetch a device array to host numpy."""
    return np.asarray(x)


def host_int(x) -> int:
    """Materialize a device scalar on the host (an explicit blocking point).

    Reference analog: reading a Legion future, e.g. ``int.from_bytes`` of the nnz
    future at ``sparse/io.py:45-47`` / ``sparse/base.py:47-48``. Every dynamic-nnz
    site goes through here so the control/device sync boundaries stay auditable —
    and countable: telemetry tallies each fetch under ``host_sync.int``, making
    the sync budget of a workload visible in ``telemetry.summary()``.
    """
    from .config import settings

    if settings.telemetry:
        from . import telemetry

        telemetry.count("host_sync.int")
    return int(x)


_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache (idempotent).

    One rule for where it lives: where ``JAX_COMPILATION_CACHE_DIR`` is
    set JAX already keeps its cache there and no code here sets a
    directory; where it is not, the cache is ``<repo>/.jax_cache``
    (fixed, gitignored — the path is part of the cache key, so a
    directory that moves never hits). Every entry point that wants
    cross-process compile reuse (``SolveSession``, ``chip_smoke.py``,
    the examples) goes through this one function.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def in_trace() -> bool:
    """True when called under an active jax trace (jit/scan/vmap body).

    Inside a trace, ops on even CONCRETE arrays return tracers, so code
    that needs a host sync (layout detection, shape materialization)
    must skip rather than raise TracerArrayConversionError. Executes no
    device op: it is called eagerly on hot paths.
    """
    from jax._src.core import trace_state_clean

    return not trace_state_clean()


def host_scope():
    """Context manager: run eager array work on the CPU backend.

    Layout detection and other one-time eager analyses are sequences of
    small eager ops; dispatched op by op to an accelerator each one pays
    a compile and a launch, and the result is wanted on the host anyway.
    Under this scope UNCOMMITTED arrays (host-built constructions)
    compute on the local CPU; arrays already committed to an accelerator
    keep their device, so no silent device->host bulk transfers are
    introduced. Needs the CPU backend beside the accelerator
    (``JAX_PLATFORMS=tpu,cpu``, or unset); with the platform list naming
    the TPU alone this raises rather than build on the chip in silence.
    """
    return jax.default_device(jax.local_devices(backend="cpu")[0])


def resident_on(a, device):
    """``a`` committed to ``device``: ``a`` ITSELF where it already is (a
    ``device_put`` would hand back another object over the same buffer, and
    what a layout cache keys on the identity of its source would miss)."""
    if getattr(a, "committed", False) and a.devices() == {device}:
        return a
    return jax.device_put(a, device)


def commit_to_exec_device(arrs):
    """Commit a tuple of arrays to the ACTIVE execution device.

    Layout caches (DIA planes, ELL index/data planes) are built under
    :func:`host_scope`; if the hot path then passes them as jit
    ARGUMENTS on an accelerator, every call re-ships them through the
    device link (~720 MB per matvec at 6000^2). The
    active device is the current ``jax.default_device`` scope if set
    (so CPU-scoped build phases keep their arrays local), else the
    backend's first device. On a CPU target this is a no-op; so is
    re-committing already-resident arrays.
    """
    target = jax.config.jax_default_device or jax.devices()[0]
    if getattr(target, "platform", "cpu") == "cpu":
        return arrs
    return tuple(resident_on(a, target) for a in arrs)
