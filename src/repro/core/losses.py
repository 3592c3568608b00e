"""IMPALA losses (policy gradient + baseline + entropy), plus the
chunked-vocab variants needed at LLM scale (the (T,B,V) logits tensor for
V=150k does not fit; we scan over sequence chunks).

Loss definitions match TorchBeast's polybeast.py:
  pg_loss       = sum_t  -log pi(a_t|s_t) * stop_grad(pg_advantage_t)
  baseline_loss = 0.5 * sum_t (vs_t - V(s_t))^2
  entropy_loss  = sum_t sum_a pi log pi          (i.e. negative entropy)
  total = pg + baseline_cost * baseline + entropy_cost * entropy
All sums over T and mean... TorchBeast sums over (T, B); we keep SUM over T
and MEAN over B (configurable via ``reduce``) — the sum convention is the
paper's, recorded in EXPERIMENTS.md §Validation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import vtrace as vtrace_lib


class ImpalaLossOutput(NamedTuple):
    total: jnp.ndarray
    pg_loss: jnp.ndarray
    baseline_loss: jnp.ndarray
    entropy_loss: jnp.ndarray
    vs_mean: jnp.ndarray
    rho_mean: jnp.ndarray
    # per-column mean |pg_advantage| — the elite-replay priority signal
    priority: jnp.ndarray = 0.0
    clear_policy_loss: jnp.ndarray = 0.0
    clear_value_loss: jnp.ndarray = 0.0


def _reduce(x, reduce):
    return jnp.sum(x) if reduce == "sum" else jnp.sum(jnp.mean(x, axis=1))


def _vtrace_fn(vtrace_impl, mesh=None):
    """Resolve the V-trace recursion implementation: the reverse-scan
    reference ('scan') or the Pallas TPU kernel ('kernel',
    kernels/vtrace.py — interpret-mode on CPU, same recursion blocked over
    128-wide batch lanes held in VMEM). ``mesh``: the learner's mesh, on
    which the kernel runs per device over its batch columns."""
    if vtrace_impl == "scan":
        return vtrace_lib.vtrace_from_importance_weights
    if vtrace_impl == "kernel":
        from repro.kernels import ops
        return functools.partial(ops.vtrace_from_importance_weights_kernel,
                                 mesh=mesh)
    raise ValueError(f"vtrace_impl must be 'scan' or 'kernel': "
                     f"{vtrace_impl!r}")


def clear_auxiliary_loss(target_lp_all, behavior_logits, values,
                         behavior_values, is_replay, *, reduce="mean"):
    """CLEAR-style behavioral + value cloning on replayed rows only
    (Rolnick et al. 2019, "Experience Replay for Continual Learning"):

      policy cloning  sum_t KL(mu || pi)         — keep pi close to the
                                                   behavior policy that
                                                   generated the replayed
                                                   data
      value cloning   0.5 * sum_t (V_mu - V)^2   — anchor V on the value
                                                   estimates RECORDED when
                                                   the data was generated
                                                   (behavior_values; None
                                                   disables the term)

    is_replay: (B,) bool column mask; fresh rows contribute nothing.
    target_lp_all/values carry gradients; behavior_logits/behavior_values
    are data.
    """
    behavior_lp = jax.nn.log_softmax(
        behavior_logits.astype(jnp.float32), -1)
    kl = jnp.sum(jnp.exp(behavior_lp) * (behavior_lp - target_lp_all),
                 axis=-1)                                   # (T, B)
    mask = is_replay.astype(jnp.float32)[None, :]           # (1, B)
    policy_cloning = _reduce(kl * mask, reduce)
    value_cloning = jnp.zeros(())
    if behavior_values is not None:
        value_cloning = 0.5 * _reduce(
            jnp.square(behavior_values - values) * mask, reduce)
    return policy_cloning, value_cloning


def impala_loss_from_logits(target_logits, behavior_logits, actions,
                            rewards, discounts, values, bootstrap_value,
                            *, baseline_cost=0.5, entropy_cost=0.01,
                            clip_rho=1.0, clip_c=1.0, reduce="mean",
                            is_replay=None, behavior_values=None,
                            clear_policy_cost=0.0, clear_value_cost=0.0,
                            vtrace_impl="scan", mesh=None):
    """Paper-faithful path (full logits, small action spaces). All (T,B,...).

    target_logits/values carry gradients; behavior_* are data.
    is_replay: optional (B,) bool mask of replayed columns; when given
    together with nonzero clear_*_cost, the CLEAR cloning terms are added
    for those columns (core/replay.py). behavior_values (T,B): the acting
    network's value estimates recorded at generation time — the
    value-cloning anchor (without it only policy cloning is applied).
    vtrace_impl: 'scan' (reverse-scan reference) or 'kernel' (the Pallas
    V-trace recursion, interpret-mode on CPU). mesh: the learner's mesh,
    if any (the kernel is not partitioned by XLA; see ``_vtrace_fn``).
    """
    target_lp_all = jax.nn.log_softmax(target_logits.astype(jnp.float32), -1)
    target_lp = jnp.take_along_axis(target_lp_all, actions[..., None],
                                    axis=-1)[..., 0]
    behavior_lp = vtrace_lib._action_log_probs(behavior_logits, actions)

    vt = _vtrace_fn(vtrace_impl, mesh)(
        jax.lax.stop_gradient(target_lp) - behavior_lp, discounts, rewards,
        jax.lax.stop_gradient(values), bootstrap_value,
        clip_rho_threshold=clip_rho, clip_c_threshold=clip_c)

    pg_loss = _reduce(-target_lp * vt.pg_advantages, reduce)
    baseline_loss = 0.5 * _reduce(jnp.square(vt.vs - values), reduce)
    probs = jnp.exp(target_lp_all)
    entropy_loss = _reduce(jnp.sum(probs * target_lp_all, axis=-1), reduce)

    total = pg_loss + baseline_cost * baseline_loss \
        + entropy_cost * entropy_loss

    clear_pc = clear_vc = jnp.zeros(())
    if is_replay is not None and (clear_policy_cost or clear_value_cost):
        clear_pc, clear_vc = clear_auxiliary_loss(
            target_lp_all, behavior_logits, values, behavior_values,
            is_replay, reduce=reduce)
        total = total + clear_policy_cost * clear_pc \
            + clear_value_cost * clear_vc

    rho = jnp.exp(jax.lax.stop_gradient(target_lp) - behavior_lp)
    priority = jnp.mean(jnp.abs(vt.pg_advantages), axis=0)     # (B,)
    return ImpalaLossOutput(total, pg_loss, baseline_loss, entropy_loss,
                            vt.vs.mean(), rho.mean(), priority,
                            clear_pc, clear_vc)


def impala_loss_from_logprobs(target_logprobs, target_entropy,
                              behavior_logprobs, rewards, discounts, values,
                              bootstrap_value, *, baseline_cost=0.5,
                              entropy_cost=0.01, clip_rho=1.0, clip_c=1.0,
                              reduce="mean", vtrace_impl="scan", mesh=None):
    """LLM-scale path: (T,B) chosen-action log-probs + per-step entropy
    (computed chunked by the caller). target_logprobs/values/target_entropy
    carry gradients. vtrace_impl, mesh as in ``impala_loss_from_logits``."""
    vt = _vtrace_fn(vtrace_impl, mesh)(
        jax.lax.stop_gradient(target_logprobs) - behavior_logprobs,
        discounts, rewards, jax.lax.stop_gradient(values), bootstrap_value,
        clip_rho_threshold=clip_rho, clip_c_threshold=clip_c)
    pg_loss = _reduce(-target_logprobs * vt.pg_advantages, reduce)
    baseline_loss = 0.5 * _reduce(jnp.square(vt.vs - values), reduce)
    entropy_loss = _reduce(-target_entropy, reduce)
    total = pg_loss + baseline_cost * baseline_loss \
        + entropy_cost * entropy_loss
    rho = jnp.exp(jax.lax.stop_gradient(target_logprobs) - behavior_logprobs)
    priority = jnp.mean(jnp.abs(vt.pg_advantages), axis=0)     # (B,)
    return ImpalaLossOutput(total, pg_loss, baseline_loss, entropy_loss,
                            vt.vs.mean(), rho.mean(), priority)


# ---------------------------------------------------------------------------
# chunked vocab head: per-token log-prob of chosen action + entropy
# ---------------------------------------------------------------------------

def chunked_logprob_entropy(hidden, unembed, actions, *, chunk=512,
                            final_softcap=None):
    """hidden: (B,S,d); unembed: (d,V); actions: (B,S) int32.

    Scans over S-chunks so the (B,chunk,V) logits stay transient.
    Returns (logprob (B,S), entropy (B,S)) — both differentiable.
    """
    b, s, d = hidden.shape
    c = min(chunk, s)
    assert s % c == 0
    n = s // c
    hs = hidden.reshape(b, n, c, d).transpose(1, 0, 2, 3)
    ac = actions.reshape(b, n, c).transpose(1, 0, 2)

    @jax.checkpoint
    def step(_, xs):
        # checkpointed: the (B,chunk,V) logits/log-softmax are recomputed in
        # the backward pass instead of being stored for every chunk.
        h, a = xs
        logits = jnp.einsum("bcd,dv->bcv", h, unembed.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        if final_softcap:
            logits = final_softcap * jnp.tanh(logits / final_softcap)
        lp = jax.nn.log_softmax(logits, axis=-1)
        alp = jnp.take_along_axis(lp, a[..., None], axis=-1)[..., 0]
        ent = -jnp.sum(jnp.exp(lp) * lp, axis=-1)
        return None, (alp, ent)

    _, (lps, ents) = jax.lax.scan(step, None, (hs, ac))
    return (lps.transpose(1, 0, 2).reshape(b, s),
            ents.transpose(1, 0, 2).reshape(b, s))


def chunked_softmax_xent(hidden, unembed, labels, *, chunk=512,
                         final_softcap=None):
    """Standard LM cross-entropy, chunked over S. Returns mean nats/token."""
    lp, _ = chunked_logprob_entropy(hidden, unembed, labels, chunk=chunk,
                                    final_softcap=final_softcap)
    return -lp.mean()
