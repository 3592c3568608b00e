"""The max-pool kernels (interpret mode on the CPU) against the VJP of
``lax.reduce_window``, and the IMPALA deep ResNet's gradient through them."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_forced
from repro.kernels import maxpool as mp
from repro.kernels import ref
from repro.models import convnet


def _reduce_window_pool(x):     # NHWC, the pool's primal
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])


def _tied(rng, shape):
    """Few distinct values, so most windows hold ties, and a constant
    region besides."""
    x = rng.integers(0, 3, shape).astype(np.float32)
    x[: shape[0] // 3, : shape[1] // 2] = 1.0
    return x


# (H, W, C, N), block: None is what ``blocks`` picks
CASES = [
    ((84, 84, 16, 3), None),
    ((84, 84, 16, 3), (14, 7, 8)),          # column halo, channel blocks
    ((42, 42, 32, 2), None),
    ((42, 42, 32, 2), (6, 3, 16)),
    ((21, 21, 32, 2), None),                # odd: 21 -> 11
    ((21, 21, 32, 2), (21, 11, 8)),
    ((21, 42, 8, 3), (14, 7, 8)),           # H != W
    ((84, 21, 8, 2), None),
    ((7, 10, 8, 2), (2, 1, 8)),
    ((3, 3, 4, 5), None),
]


@pytest.mark.parametrize("shape,block", CASES,
                         ids=[f"{s[0]}x{s[1]}x{s[2]}-{b}" for s, b in CASES])
def test_maxpool_kernels_match_reduce_window_vjp(shape, block):
    """The pooled max and the winners agree exactly; dx agrees with
    select_and_scatter's to 1e-6 (the order of float sums)."""
    h, w, c, n = shape
    rng = np.random.default_rng(sum(shape))
    x = jnp.asarray(_tied(rng, shape))
    out, idx = mp.maxpool_fwd(x, block=block, interpret=True)

    want, vjp = jax.vjp(_reduce_window_pool, x.transpose(3, 0, 1, 2))
    want = want.transpose(1, 2, 3, 0)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(idx, ref.ref_maxpool_fwd(x)[1])
    assert out.shape == idx.shape == ((h + 1) // 2, (w + 1) // 2, c, n)
    assert idx.dtype == jnp.int8

    dy = jnp.asarray(rng.normal(size=want.shape).astype(np.float32))
    dx = mp.maxpool_bwd(idx, dy, hw=(h, w), block=block, interpret=True)
    dx_want = vjp(dy.transpose(3, 0, 1, 2))[0].transpose(1, 2, 3, 0)
    np.testing.assert_allclose(dx, dx_want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ref.ref_maxpool_bwd(idx, dy, (h, w)),
                               dx_want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("hw", [(84, 84), (42, 42), (21, 21)])
def test_maxpool_blocks_fit_vmem_at_learner_batch(hw):
    """At the learner's 2,592 frames each step's blocks fit the VMEM
    target, and tile whole multiples of the columns and channels."""
    c = 16 if hw[0] == 84 else 32
    bw, k, cb = mp.blocks(*hw, c, 2592)
    wo = mp.pooled_size(hw[1])
    assert wo % k == 0 and c % cb == 0 and bw == min(2 * k, hw[1])
    assert mp._footprint(bw, k, cb, 2592, 4) <= mp.VMEM_TARGET


def _deep(obs_shape=(20, 20, 4)):
    init_fn, apply_fn = convnet.impala_deep(obs_shape, 6)
    params, _ = convnet.init_agent(init_fn, jax.random.PRNGKey(0))
    obs = jax.random.uniform(jax.random.PRNGKey(1), (3, 2) + obs_shape)
    return apply_fn, params, obs


def _loss(apply_fn):
    def loss(params, obs):
        out = apply_fn(params, obs)
        return (jnp.sum(jnp.sin(out.policy_logits))
                + jnp.sum(out.baseline ** 2))
    return loss


def test_impala_deep_grad_through_kernels(monkeypatch):
    """The parameter gradient through the kernel VJP equals the one
    through reduce_window's own VJP."""
    apply_fn, params, obs = _deep()
    got = jax.jit(jax.grad(_loss(apply_fn)))(params, obs)
    monkeypatch.setattr(convnet, "_maxpool", _reduce_window_pool)
    want = jax.jit(jax.grad(_loss(apply_fn)))(params, obs)
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-6)


def _primitives(jaxpr):
    """Every equation's primitive name, and each pallas_call's kernel
    name, through the sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name += ":" + str(eqn.params["name"])
        out.append(name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out.extend(_primitives(sub))
    return out


def test_only_differentiation_runs_the_kernels():
    """The actors' forward pools with reduce_window; the gradient runs
    maxpool_fwd and maxpool_bwd, one each per section, under the
    ``maxpool`` scope."""
    apply_fn, params, obs = _deep()
    forward = _primitives(jax.make_jaxpr(apply_fn)(params, obs).jaxpr)
    assert forward.count("reduce_window_max") == 3
    assert not [p for p in forward if p.startswith("pallas_call")]
    grad = _primitives(jax.make_jaxpr(jax.grad(_loss(apply_fn)))(
        params, obs).jaxpr)
    assert grad.count("pallas_call:maxpool_fwd") == 3
    assert grad.count("pallas_call:maxpool_bwd") == 3
    assert not [p for p in grad if "select_and_scatter" in p]
    text = jax.jit(jax.grad(_loss(apply_fn))).lower(
        params, obs).compile().as_text()
    scoped = {n for n in re.findall(r'op_name="([^"]*)"', text)
              if re.search(r"(^|[/(])maxpool($|[/)])", n)}
    assert {n for n in scoped if "transpose(jvp(" not in n}
    assert {n for n in scoped if "transpose(jvp(" in n}


_MESH_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.atari_impala import small_train
from repro.core import learner as L
from repro.distributed.sharding import RL_AGENT_RULES
from repro.launch.mesh import make_data_mesh
from repro.models.convnet import impala_deep, init_agent
from repro.optim import make_optimizer

T, B, A, OBS = 3, 8, 6, (12, 12, 4)
tc = small_train(unroll_length=T, batch_size=B, total_steps=50)
init_fn, apply_fn = impala_deep(OBS, A, channels=(8, 8, 8), fc=16)
params0, _ = init_agent(init_fn, jax.random.PRNGKey(0))
opt = make_optimizer(tc)
rng = np.random.default_rng(0)
batch = {
    "obs": rng.random((T + 1, B) + OBS).astype(np.float32),
    "action": rng.integers(0, A, (T, B)).astype(np.int32),
    "behavior_logits": rng.normal(0, 1, (T, B, A)).astype(np.float32),
    "reward": rng.normal(0, 1, (T, B)).astype(np.float32),
    "done": rng.random((T, B)) > 0.9,
}

def run(n):
    mesh = make_data_mesh(n)
    step = jax.jit(L.make_train_step(apply_fn, opt, tc, mesh=mesh,
                                     rules=RL_AGENT_RULES))
    params = jax.device_put(params0, NamedSharding(mesh, PartitionSpec()))
    b = {k: jax.device_put(jnp.asarray(v), NamedSharding(
             mesh, PartitionSpec(*([None, "data"] + [None] * (v.ndim - 2)))))
         for k, v in batch.items()}
    text = step.lower(params, opt.init(params), jnp.int32(0), b).as_text()
    params, _, m = step(params, opt.init(params), jnp.int32(0), b)
    return float(m["loss"]), jax.device_get(params), text

l1, p1, _ = run(1)
l4, p4, text = run(4)
assert "shard_map" in text or "sdy.manual_computation" in text, text[:2000]
np.testing.assert_allclose(l1, l4, rtol=1e-5, atol=1e-6)
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
print("ok", l1, l4)
"""


def test_impala_deep_mesh4_matches_mesh1():
    """Under a 4-way data mesh each device pools its own frames (the
    kernels under shard_map), and the step equals the one-device step."""
    proc = run_forced(script=_MESH_SCRIPT, devices=4, timeout=600)
    assert "ok" in proc.stdout
