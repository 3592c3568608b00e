"""Plain IMPALA learner step: the deep ResNet, V-trace, the IMPALA loss and
TF-style RMSProp with global-norm clipping, in float32 at "highest" matmul
precision. It imports nothing of the program.

Convolutions are written as patch extraction and one matrix product per
layer, over blocks of frames (``lax.map`` with a checkpoint per block),
so the full learner batch fits next to nothing else on one chip.

``low=True`` gives the control, one precision step below the program's
(whose float32 products run as one bfloat16 pass on a TPU, forward and
backward): every operand of a convolution or dense product, and of the
products that make its gradients, is rounded to float8 e4m3 under a
per-tensor scale, as an fp8 training path feeds the matrix unit;
products accumulate, and everything else stays, in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def fp8(x):
    """``x`` rounded to float8 e4m3, its largest magnitude mapped to the
    format's largest (448)."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


@jax.custom_vjp
def fp8_dot(x, w):
    """x (M,K) @ w (K,O) with fp8 operands, in the gradients' products
    too."""
    return _dot(fp8(x), fp8(w))


def _fp8_dot_fwd(x, w):
    qx, qw = fp8(x), fp8(w)
    return _dot(qx, qw), (qx, qw)


def _fp8_dot_bwd(res, ct):
    qx, qw = res
    qct = fp8(ct)
    return _dot(qct, qw.T), _dot(qx.T, qct)


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def _product(x, w, low):
    """x (..., K) @ w (K, O)."""
    flat = x.reshape(-1, x.shape[-1])
    y = fp8_dot(flat, w) if low else _dot(flat, w)
    return y.reshape(x.shape[:-1] + w.shape[-1:])


def _conv3x3(x, w, b, low=False):
    """SAME 3x3 stride-1 convolution. x (N,H,W,C), w (3,3,C,O)."""
    n, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, i:i + h, j:j + wd, :] for i in range(3) for j in range(3)],
        axis=-1)                                     # (N,H,W,9C), (i,j,c)
    return _product(patches, w.reshape(9 * c, -1), low) + b


def _maxpool3x3s2(x):
    """3x3 window, stride 2, one row/column of -inf padding each side."""
    n, h, w, c = x.shape
    ho, wo = -(-h // 2), -(-w // 2)
    xp = jnp.pad(x, ((0, 0), (1, 1 + 2 * ho - h), (1, 1 + 2 * wo - w),
                     (0, 0)), constant_values=-jnp.inf)
    out = None
    for i in range(3):
        for j in range(3):
            s = xp[:, i:i + 2 * ho:2, j:j + 2 * wo:2, :]
            out = s if out is None else jnp.maximum(out, s)
    return out


def _dense(x, p, low=False):
    return _product(x, p["w"], low) + p["b"]


def network(params, obs, low=False):
    """obs (N,H,W,C) -> (policy logits (N,A), baseline (N,)), float32."""
    x = obs.astype(jnp.float32)
    for sec in params["sections"]:
        x = _conv3x3(x, sec["conv"]["w"], sec["conv"]["b"], low)
        x = _maxpool3x3s2(x)
        for res in sec["res"]:
            y = _conv3x3(jax.nn.relu(x), res["c1"]["w"], res["c1"]["b"], low)
            y = _conv3x3(jax.nn.relu(y), res["c2"]["w"], res["c2"]["b"], low)
            x = x + y
    x = jax.nn.relu(x).reshape(x.shape[0], -1)
    x = jax.nn.relu(_dense(x, params["fc"], low))
    logits = _dense(x, params["policy"], low)
    baseline = _dense(x, params["baseline"], low)[:, 0]
    return logits, baseline


def blocked_network(params, obs, block, low=False):
    """``network`` over obs (T1,B,...) in blocks of ``block`` frames."""
    lead = obs.shape[:2]
    flat = obs.reshape((-1,) + obs.shape[2:])
    nb = flat.shape[0] // block
    fn = jax.checkpoint(lambda o: network(params, o, low))
    logits, base = jax.lax.map(fn, flat.reshape((nb, block)
                                                + flat.shape[1:]))
    return (logits.reshape(lead + logits.shape[-1:]), base.reshape(lead))


def vtrace(log_rhos, discounts, rewards, values, bootstrap, clip_rho,
           clip_c):
    """V-trace targets and advantages (time-major (T,B)), Python-unrolled
    backward recursion."""
    rhos = jnp.exp(log_rhos)
    crho = jnp.minimum(clip_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)
    v_next = jnp.concatenate([values[1:], bootstrap[None]], 0)
    deltas = crho * (rewards + discounts * v_next - values)
    acc = jnp.zeros_like(bootstrap)
    out = []
    for t in reversed(range(values.shape[0])):
        acc = deltas[t] + discounts[t] * cs[t] * acc
        out.append(acc)
    vs = values + jnp.stack(out[::-1])
    vs_next = jnp.concatenate([vs[1:], bootstrap[None]], 0)
    pg_adv = crho * (rewards + discounts * vs_next - values)
    return jax.lax.stop_gradient(vs), jax.lax.stop_gradient(pg_adv)


def loss(params, batch, hp, block, low=False):
    """IMPALA loss: sum over time, mean over the batch. Returns (total,
    (pg, baseline, entropy))."""
    logits, base = blocked_network(params, batch["obs"], block, low)
    t = batch["action"].shape[0]
    target_logits, values, bootstrap = logits[:t], base[:t], base[t]
    bootstrap = jax.lax.stop_gradient(bootstrap)
    lp_all = jax.nn.log_softmax(target_logits, -1)
    target_lp = jnp.take_along_axis(lp_all, batch["action"][..., None],
                                    -1)[..., 0]
    blp_all = jax.nn.log_softmax(batch["behavior_logits"], -1)
    behavior_lp = jnp.take_along_axis(blp_all, batch["action"][..., None],
                                      -1)[..., 0]
    discounts = (1.0 - batch["done"].astype(jnp.float32)) * hp["discount"]
    vs, pg_adv = vtrace(jax.lax.stop_gradient(target_lp) - behavior_lp,
                        discounts, batch["reward"],
                        jax.lax.stop_gradient(values), bootstrap,
                        hp["vtrace_rho_clip"], hp["vtrace_c_clip"])
    red = lambda x: jnp.sum(jnp.mean(x, axis=1))  # noqa: E731
    pg = red(-target_lp * pg_adv)
    bl = 0.5 * red(jnp.square(vs - values))
    ent = red(jnp.sum(jnp.exp(lp_all) * lp_all, -1))
    total = pg + hp["baseline_cost"] * bl + hp["entropy_cost"] * ent
    return total, (pg, bl, ent)


@functools.partial(jax.jit, static_argnames=("hp_items", "block", "low"))
def train_step(params, ms, step, batch, *, hp_items, block, low=False):
    """One learner step (``low``: the fp8 control). Returns (params, ms,
    total loss, loss parts, clipped gradients)."""
    hp = dict(hp_items)
    (total, parts), grads = jax.value_and_grad(loss, has_aux=True)(
        params, batch, hp, block, low)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    frac = jnp.clip(1.0 - step.astype(jnp.float32) / hp["total_steps"],
                    0.0, 1.0)
    lr = hp["learning_rate"] * frac
    decay, eps = hp["rmsprop_decay"], hp["rmsprop_eps"]
    ms = jax.tree.map(lambda m, g: decay * m + (1 - decay) * g * g, ms,
                      grads)
    params = jax.tree.map(lambda p, g, m: p - lr * g / jnp.sqrt(m + eps),
                          params, grads, ms)
    return params, ms, total, parts, grads
