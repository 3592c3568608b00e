"""Interpret-or-compile selection for the Pallas kernels by backend."""

import warnings

import jax

_stats = {"explicit": 0, "compiled": 0, "fallbacks": 0}


def resolve_interpret(interpret=None):
    """Resolve a caller's ``interpret=`` request against the backend.

    ``None`` (the default everywhere) auto-selects: compiled on TPU,
    interpret mode elsewhere — the kernels target Mosaic-TPU, and
    interpret mode executes the same kernel body under the CPU/GPU
    backend so the ``kernel`` impls stay runnable (and parity-testable)
    in CI. The fallback warns once per process, and every resolution is
    counted: ``resolve_interpret.stats()`` lets tests and the static
    auditor assert that no path which requested ``impl=kernel`` fell
    back to interpret mode *silently*.
    """
    if interpret is not None:
        _stats["explicit"] += 1
        return interpret
    if jax.default_backend() == "tpu":
        _stats["compiled"] += 1
        return False
    if _stats["fallbacks"] == 0:
        warnings.warn(
            "Pallas kernels: no TPU backend detected "
            f"({jax.default_backend()}); running in interpret mode "
            "(slow, validation only).", stacklevel=2)
    _stats["fallbacks"] += 1
    return True


def _stats_snapshot():
    return dict(_stats)


def _stats_reset():
    for k in _stats:
        _stats[k] = 0


resolve_interpret.stats = _stats_snapshot
resolve_interpret.reset_stats = _stats_reset
