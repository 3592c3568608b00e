"""Production mesh construction (TPU v5e target).

Single pod: 256 chips as (16, 16) ("data", "model").
Multi-pod:  2 pods x 256 chips as (2, 16, 16) ("pod", "data", "model") —
the "pod" axis is an additional data axis; gradient all-reduce crosses the
inter-pod links once per step (DESIGN.md §7).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any device query).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types. ``devices``: optional
    explicit device array (defaults to all local devices, as
    ``jax.make_mesh`` does)."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is not None:
        import numpy as np
        devices = np.asarray(devices).reshape(shape)
        return jax.sharding.Mesh(devices, axes, axis_types=axis_types)
    return jax.make_mesh(shape, axes, axis_types=axis_types)


def make_abstract_mesh(shape, axes):
    """A device-free mesh of the same axis types as ``make_mesh`` — what
    the static auditors and spec tests trace against."""
    return jax.sharding.AbstractMesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D 'data' mesh (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


def make_mesh2d(data=1, model=1, devices=None):
    """The first ``data * model`` devices as a 2-D ("data", "model") mesh —
    the LM-path learner mesh (``--mesh-data N --mesh-model M``).

    ``devices`` defaults to the GLOBAL device set (``jax.devices()``), so
    under a ``jax.distributed`` bootstrap (launch/multihost.py, or
    ``train.py --coordinator``) the same call builds the whole-pod mesh;
    on CPU force host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``. At
    ``(data=1, model=1)`` the learner programs built on this mesh are
    bit-identical to the unmeshed ones (tests/test_mesh2d.py).
    """
    devices = jax.devices() if devices is None else list(devices)
    n = data * model
    if n > len(devices):
        raise ValueError(
            f"mesh ({data}, {model}) needs {n} devices but only "
            f"{len(devices)} visible (on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count)")
    return make_mesh((data, model), ("data", "model"), devices=devices[:n])


def make_data_mesh(n=None):
    """The first ``n`` local devices as a 1-D ("data",) mesh — the
    data-parallel RL learner mesh (``--mesh-data N``). On CPU, run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get N>1."""
    devices = jax.devices()
    n = len(devices) if n is None else n
    if n > len(devices):
        raise ValueError(
            f"--mesh-data {n} but only {len(devices)} devices visible "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return make_mesh((n,), ("data",), devices=devices[:n])


# Hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per chip per direction)
HBM_BYTES = 16 * 1024**3      # 16 GiB per chip
VMEM_BYTES = 128 * 1024**2    # ~128 MiB vector memory (v5e)
