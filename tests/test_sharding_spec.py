"""Property-style spec tests for the logical-axes -> mesh mapping
(``logical_to_mesh`` / ``batch_axes_spec``), pinning the contract every
new mesh combination must obey. Seeded sweeps stand in for hypothesis, as
in test_moe.py — the suite runs on a bare install.

Properties (over every rules table, arbitrary 1-D/2-D/3-D meshes via
``jax.sharding.AbstractMesh`` — no real devices needed — and the full
heterogeneous arch zoo):
  * every produced PartitionSpec only names LIVE mesh axes;
  * no mesh axis is used twice within one spec;
  * with a shape given, a mapped dimension is always divisible by the
    product of its mesh-axis sizes (non-divisible mappings replicate);
  * ``batch_axes_spec`` shards exactly the batch dim over the data-like
    axes, or returns None (replicate) when non-divisible / size-1.
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced_config
from repro.distributed.sharding import (RL_AGENT_RULES, RULE_SETS,
                                        batch_axes_spec, data_axes,
                                        logical_to_mesh)
from repro.launch.mesh import make_abstract_mesh
from repro.models import model as model_lib
from repro.models.common import split_params

# logical-axis vocabulary: every axis name any rules table knows, minus
# "attn_pref" (a preference flag consumed by constrain_attention, never a
# parameter axis), plus names no table maps (must replicate).
_LOGICAL = sorted({ax for rules in RULE_SETS.values() for ax in rules}
                  - {"attn_pref"}) + ["layers", "unknown_axis"]
_RULES_NAMES = sorted(RULE_SETS)


def _mesh(data=1, model=1, pod=None):
    if pod:
        return make_abstract_mesh((pod, data, model), ("pod", "data", "model"))
    return make_abstract_mesh((data, model), ("data", "model"))


def _assert_valid(spec, mesh, shape=None):
    """The executable spec contract (module docstring)."""
    used = []
    for i, part in enumerate(spec):
        if part is None:
            continue
        axes = part if isinstance(part, tuple) else (part,)
        for a in axes:
            assert a in mesh.axis_names, f"{spec} names dead axis {a!r}"
            used.append(a)
        if shape is not None:
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert shape[i] % size == 0, \
                f"{spec}: dim {i} ({shape[i]}) not divisible by {size}"
    assert len(used) == len(set(used)), f"{spec} reuses a mesh axis"
    if shape is not None:
        assert len(spec) <= len(shape)


_MESHES = [_mesh(1, 1), _mesh(2, 1), _mesh(1, 2), _mesh(2, 2),
           _mesh(4, 2), _mesh(2, 4), _mesh(8, 1), _mesh(1, 16),
           _mesh(2, 16, pod=2), _mesh(16, 16)]


@pytest.mark.parametrize("seed", [0, 7, 101, 577, 1000])
def test_logical_to_mesh_properties_random_sweep(seed):
    """Random (rules, mesh, logical axes, shape) draws: the produced spec
    always satisfies the contract, with and without shape-aware dropping
    and with the fallback-model pass on."""
    rng = np.random.default_rng(seed)
    for _ in range(150):
        rules = RULE_SETS[_RULES_NAMES[rng.integers(len(_RULES_NAMES))]]
        mesh = _MESHES[rng.integers(len(_MESHES))]
        ndim = int(rng.integers(1, 5))
        axes = tuple(_LOGICAL[i]
                     for i in rng.integers(0, len(_LOGICAL), size=ndim))
        shape = tuple(int(rng.choice([1, 2, 3, 4, 6, 8, 16, 48, 56, 512]))
                      for _ in range(ndim))
        # axis-validity holds even without a shape (no divisibility pass)
        _assert_valid(logical_to_mesh(axes, mesh, rules), mesh)
        for fallback in (False, True):
            spec = logical_to_mesh(axes, mesh, rules, shape,
                                   fallback_model=fallback and ndim > 1)
            _assert_valid(spec, mesh, shape)


@pytest.mark.parametrize("seed", [0, 7, 101, 577, 1000])
def test_batch_axes_spec_properties_random_sweep(seed):
    """batch_axes_spec shards exactly the requested batch dim over the
    data-like axes, or replicates when the batch does not divide."""
    rng = np.random.default_rng(seed)
    for _ in range(150):
        rules = RULE_SETS[_RULES_NAMES[rng.integers(len(_RULES_NAMES))]]
        mesh = _MESHES[rng.integers(len(_MESHES))]
        ndim = int(rng.integers(1, 6))
        shape = tuple(int(rng.choice([1, 2, 3, 4, 6, 8, 16, 32, 64]))
                      for _ in range(ndim))
        bdim = int(rng.integers(0, ndim))
        spec = batch_axes_spec(mesh, rules, ndim, shape, bdim)
        daxes = data_axes(mesh)
        dsize = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
        if dsize == 1 or shape[bdim] % dsize != 0:
            assert spec is None
        else:
            parts = list(spec) + [None] * (ndim - len(spec))
            assert parts[bdim] == (daxes if len(daxes) > 1 else daxes[0])
            assert all(p is None for i, p in enumerate(parts) if i != bdim)
            _assert_valid(spec, mesh, shape)


# ---------------------------------------------------------------------------
# the real parameter trees: every arch config x rules table x mesh


_ARCHS = ["qwen3-4b", "mixtral-8x7b", "zamba2-2.7b", "xlstm-125m",
          "deepseek-coder-33b", "gemma2-27b", "llama-3.2-vision-90b",
          "granite-moe-1b-a400m"]


def _axes_shapes(cfg):
    box = {}

    def f():
        vals, axes = split_params(
            model_lib.model_init(jax.random.PRNGKey(0), cfg))
        box["axes"] = axes
        return vals

    shapes = jax.eval_shape(f)
    return box["axes"], shapes


@pytest.mark.parametrize("arch", _ARCHS)
def test_arch_param_specs_valid_on_every_mesh(arch):
    """Heterogeneous archs (grouped-KV attention, MoE, SSM, xLSTM, VLM
    cross-attention): every parameter's spec obeys the contract on every
    mesh under every rules table — kv_heads=2 on a 16-way model axis must
    replicate, never crash or shard unevenly."""
    cfg = get_reduced_config(arch)
    axes_tree, shapes_tree = _axes_shapes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(a, str) for a in x)
    ax_leaves = jax.tree.leaves(axes_tree, is_leaf=is_axes)
    sh_leaves = jax.tree.leaves(shapes_tree)
    assert len(ax_leaves) == len(sh_leaves) > 0
    for rules_name in ("megatron", "fsdp", "seqpar", "expert", "rl_agent"):
        rules = RULE_SETS[rules_name]
        for mesh in _MESHES:
            for ax, sh in zip(ax_leaves, sh_leaves):
                spec = logical_to_mesh(
                    ax, mesh, rules, sh.shape,
                    fallback_model=len(sh.shape) > 1)
                _assert_valid(spec, mesh, sh.shape)


def test_rl_agent_rules_on_2d_mesh():
    """RL_AGENT_RULES stay valid on the 2-D mesh: conv/fc params fully
    replicated (never touching "model"), batch over the data axes only."""
    mesh = _mesh(4, 2)
    for axes, shape in [(("conv_h", "conv_w", "conv_in", "conv_out"),
                         (3, 3, 32, 64)),
                        (("fc_in", "fc_out"), (288, 128))]:
        assert logical_to_mesh(axes, mesh, RL_AGENT_RULES, shape) == P()
    assert logical_to_mesh(("act_batch",), mesh, RL_AGENT_RULES, (64,)) \
        == P("data")
    assert batch_axes_spec(mesh, RL_AGENT_RULES, 2, (6, 9), 0) is None
    pod = _mesh(2, 2, pod=2)
    assert logical_to_mesh(("act_batch",), pod, RL_AGENT_RULES, (64,)) \
        == P(("pod", "data"))
