"""IMPALA learner steps.

``make_train_step``      — paper-faithful agent path (full behavior logits,
                           conv/small-action agents; TorchBeast polybeast.py
                           learner loop body).
``make_lm_train_step``   — LLM-policy path (tokens are actions; chosen-action
                           behavior log-probs; chunked vocab head). This is
                           the program lowered for the ``train_4k`` shape.

Both return pure functions suitable for jax.jit/pjit:
  (params, opt_state, step, batch[, extras]) -> (params, opt_state, metrics)
Gradient synchronisation across the mesh data/pod axes comes from sharding
propagation (grads of replicated params -> all-reduce), the TPU analogue of
TorchBeast's multi-learner-thread hogwild updates (DESIGN.md §1).
"""

from __future__ import annotations

import contextlib
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.models import model as model_lib
from repro.optim.optimizers import apply_updates


def _make_shard_fns(mesh, rules):
    """(batch constrainer, grad constrainer, model-call context factory)
    for a (mesh, rules) context; identities when no mesh is given (the
    single-device path compiles to the exact same program as before). The
    context lets the model's Pallas kernels run per device
    (``models/convnet._maxpool``)."""
    if mesh is None:
        return ((lambda batch: batch), (lambda grads: grads),
                contextlib.nullcontext)
    from repro.distributed import sharding as sharding_lib
    if rules is None:
        rules = sharding_lib.RL_AGENT_RULES
    return (lambda batch: sharding_lib.shard_rollout(batch, mesh, rules),
            lambda grads: sharding_lib.replicate(grads, mesh),
            lambda: sharding_lib.use_rules(mesh, rules))


def _make_lm_mesh_fns(mesh, rules):
    """(trace-context factory, batch constrainer) for the LM steps under a
    2-D ("data","model") mesh; both identity when no mesh is given (the
    single-device path compiles to the exact same program as before).

    The context activates the (mesh, rules) thread-local so the model's
    ``constrain()`` calls shard activations over "model"; the batch
    constrainer pins the token batch's leading B dimension to the data
    axes (distributed/sharding.py::shard_lm_batch).
    """
    if mesh is None:
        return contextlib.nullcontext, (lambda batch: batch)
    from repro.distributed import sharding as sharding_lib
    if rules is None:
        rules = sharding_lib.MEGATRON_RULES
    return (lambda: sharding_lib.use_rules(mesh, rules),
            lambda batch: sharding_lib.shard_lm_batch(batch, mesh, rules))


def _optimizer_step(opt, grads, opt_state, params, step):
    """The update of the RL learner steps, under the ``optimizer`` scope
    that names its ops in a device trace."""
    with jax.named_scope("optimizer"):
        updates, opt_state = opt.update(grads, opt_state, params, step)
        return apply_updates(params, updates), opt_state


def make_train_step(agent_apply: Callable, opt, train_cfg, *,
                    mesh=None, rules=None, vtrace_impl="scan"):
    """Paper-faithful IMPALA learner step over a rollout batch.

    batch: time-major dict (see core/rollout.py):
      obs (T+1,B,...), action (T,B), behavior_logits (T,B,A),
      reward (T,B), done (T,B) [, is_replay (B,) — ReplaySource batches]

    With an ``is_replay`` mask present, the CLEAR cloning terms
    (losses.clear_auxiliary_loss) are applied to the replayed columns at
    ``train_cfg.clear_policy_cost`` / ``clear_value_cost``, and the
    reported ``reward_per_step`` covers the fresh columns only (replayed
    rewards are not new environment signal).

    mesh/rules: optional data-parallel context (distributed/sharding.py).
    The batch is constrained to shard its B dimension over the mesh data
    axes and the gradients to be replicated — the cross-device all-reduce
    falls out of sharding propagation (module docstring).
    vtrace_impl: 'scan' or 'kernel' (the Pallas V-trace recursion).
    """
    shard_batch, shard_grads, model_ctx = _make_shard_fns(mesh, rules)

    def loss_fn(params, batch):
        with jax.named_scope("learner_forward"), model_ctx():
            out = agent_apply(params, batch["obs"])   # (T+1, B, ...)
        with jax.named_scope("loss"):
            target_logits = out.policy_logits[:-1]
            values = out.baseline[:-1]
            bootstrap = jax.lax.stop_gradient(out.baseline[-1])
            discounts = (~batch["done"]).astype(jnp.float32) \
                * train_cfg.discount
            loss_out = losses.impala_loss_from_logits(
                target_logits, batch["behavior_logits"], batch["action"],
                batch["reward"], discounts, values, bootstrap,
                baseline_cost=train_cfg.baseline_cost,
                entropy_cost=train_cfg.entropy_cost,
                clip_rho=train_cfg.vtrace_rho_clip,
                clip_c=train_cfg.vtrace_c_clip,
                is_replay=batch.get("is_replay"),
                behavior_values=batch.get("behavior_value"),
                clear_policy_cost=train_cfg.clear_policy_cost,
                clear_value_cost=train_cfg.clear_value_cost,
                vtrace_impl=vtrace_impl, mesh=mesh)
        return loss_out.total, loss_out

    def train_step(params, opt_state, step, batch):
        batch = shard_batch(batch)
        grads, loss_out = jax.grad(loss_fn, has_aux=True)(params, batch)
        grads = shard_grads(grads)
        params, opt_state = _optimizer_step(opt, grads, opt_state, params,
                                            step)
        if "is_replay" in batch:
            fresh = (~batch["is_replay"]).astype(jnp.float32)[None, :]
            reward_per_step = (batch["reward"] * fresh).sum() \
                / jnp.maximum(fresh.sum() * batch["reward"].shape[0], 1.0)
        else:
            reward_per_step = batch["reward"].mean()
        metrics = {
            "loss": loss_out.total,
            "pg_loss": loss_out.pg_loss,
            "baseline_loss": loss_out.baseline_loss,
            "entropy_loss": loss_out.entropy_loss,
            "vs_mean": loss_out.vs_mean,
            "rho_mean": loss_out.rho_mean,
            "reward_per_step": reward_per_step,
            "priority": loss_out.priority,
        }
        if "is_replay" in batch:
            metrics["clear_policy_loss"] = loss_out.clear_policy_loss
            metrics["clear_value_loss"] = loss_out.clear_value_loss
        return params, opt_state, metrics

    return train_step


def make_recurrent_train_step(agent_apply, opt, train_cfg, *,
                              mesh=None, rules=None, vtrace_impl="scan"):
    """IMPALA learner for recurrent agents: re-runs the LSTM over the
    unroll from the stored initial core_state (TorchBeast's learner does
    exactly this), then V-trace as usual. batch adds "core_state".
    mesh/rules/vtrace_impl as in ``make_train_step``."""
    shard_batch, shard_grads, model_ctx = _make_shard_fns(mesh, rules)

    def loss_fn(params, batch):
        def step(core_state, xs):
            obs, pre_done = xs
            out = agent_apply(params, obs, core_state, pre_done)
            return out.core_state, (out.policy_logits, out.baseline)

        # re-run the recurrence over the T+1 observations from the stored
        # initial core_state; pre_done[t] zeroes the state exactly where
        # the actor did (fresh-episode observations)
        with jax.named_scope("learner_forward"), model_ctx():
            _, (logits, baselines) = jax.lax.scan(
                step, batch["core_state"], (batch["obs"], batch["pre_done"]))
        with jax.named_scope("loss"):
            t = batch["action"].shape[0]
            target_logits = logits[:t]
            values = baselines[:t]
            bootstrap = jax.lax.stop_gradient(baselines[t])
            discounts = (~batch["done"]).astype(jnp.float32) \
                * train_cfg.discount
            loss_out = losses.impala_loss_from_logits(
                target_logits, batch["behavior_logits"], batch["action"],
                batch["reward"], discounts, values, bootstrap,
                baseline_cost=train_cfg.baseline_cost,
                entropy_cost=train_cfg.entropy_cost,
                clip_rho=train_cfg.vtrace_rho_clip,
                clip_c=train_cfg.vtrace_c_clip,
                vtrace_impl=vtrace_impl, mesh=mesh)
        return loss_out.total, loss_out

    def train_step(params, opt_state, step, batch):
        batch = shard_batch(batch)
        grads, loss_out = jax.grad(loss_fn, has_aux=True)(params, batch)
        grads = shard_grads(grads)
        params, opt_state = _optimizer_step(opt, grads, opt_state, params,
                                            step)
        metrics = {"loss": loss_out.total, "pg_loss": loss_out.pg_loss,
                   "entropy_loss": loss_out.entropy_loss,
                   "reward_per_step": batch["reward"].mean()}
        return params, opt_state, metrics

    return train_step


def make_lm_train_step(cfg, opt, train_cfg, loss_chunk=512,
                       grad_constraint=None, vtrace_impl="scan",
                       mesh=None, rules=None):
    """IMPALA learner step for LLM policies (DESIGN.md §2).

    grad_constraint: optional fn(grads)->grads applied right after jax.grad
    — the launcher passes a sharding constraint here (grads pinned to the
    param shardings for the Megatron layout, or a ZeRO-2 constraint so the
    gradient all-reduce becomes a reduce-scatter and the fp32 optimizer
    temporaries stay sharded over the data axes).
    vtrace_impl: 'scan' or 'kernel' (the Pallas V-trace recursion).
    Attention/SSD impls come from ``cfg.attn_impl`` / ``cfg.ssd_impl``,
    resolved once at the CLI boundary via ``configs.base.ImplContext``
    ('kernel' selects the Pallas flash kernel).
    mesh/rules: optional 2-D ("data","model") context
    (distributed/sharding.py; rules default MEGATRON_RULES). The token
    batch is constrained to shard B over the data axes and the model's
    ``constrain()`` calls activate (params/activations over "model"); the
    cross-data-axis gradient all-reduce falls out of sharding propagation,
    exactly as in ``make_train_step``. At mesh (1, 1) the compiled program
    is bit-identical to the unmeshed one (tests/test_mesh2d.py).

    batch (batch-major; transposed internally for V-trace):
      tokens            (B, S+1) int32   obs[0..S]; actions are tokens[1:]
      behavior_logprob  (B, S) float32   mu(a_t|s_t) of the generating policy
      reward            (B, S) float32
      done              (B, S) bool
      [vision]          (B, Sv, d)       VLM patch embeddings (stub)
    """
    mesh_ctx, shard_batch = _make_lm_mesh_fns(mesh, rules)

    def loss_fn(params, batch):
        tokens = batch["tokens"]          # (B, S+1); model sees first S
        vision = batch.get("vision")
        # hidden[t] is the state after consuming token t => predicts t+1.
        # Forward over tokens[:, :-1] keeps S divisible by the chunk sizes.
        hidden, aux, _ = model_lib.forward(params, tokens[:, :-1], cfg=cfg,
                                           vision=vision)
        actions = tokens[:, 1:]
        unembed = model_lib.unembed_matrix(params, cfg)
        logprob, entropy = losses.chunked_logprob_entropy(
            hidden, unembed, actions, chunk=loss_chunk,
            final_softcap=cfg.final_logit_softcap)
        values_all = model_lib.baseline_from_hidden(params, cfg, hidden)
        bootstrap = jnp.zeros((tokens.shape[0],), jnp.float32)

        tm = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731  batch->time major
        discounts = (~batch["done"]).astype(jnp.float32) * train_cfg.discount
        loss_out = losses.impala_loss_from_logprobs(
            tm(logprob), tm(entropy), tm(batch["behavior_logprob"]),
            tm(batch["reward"]), tm(discounts), tm(values_all), bootstrap,
            baseline_cost=train_cfg.baseline_cost,
            entropy_cost=train_cfg.entropy_cost,
            clip_rho=train_cfg.vtrace_rho_clip,
            clip_c=train_cfg.vtrace_c_clip,
            vtrace_impl=vtrace_impl, mesh=mesh)
        lb, zl, _ = aux
        total = loss_out.total + cfg.router_aux_weight * lb \
            + cfg.router_z_weight * zl
        return total, loss_out

    def train_step(params, opt_state, step, batch):
        with mesh_ctx():
            batch = shard_batch(batch)
            grads, loss_out = jax.grad(loss_fn, has_aux=True)(params, batch)
            if grad_constraint is not None:
                grads = grad_constraint(grads)
            updates, opt_state = opt.update(grads, opt_state, params, step)
            params = apply_updates(params, updates)
        metrics = {
            "loss": loss_out.total,
            "pg_loss": loss_out.pg_loss,
            "baseline_loss": loss_out.baseline_loss,
            "entropy_loss": loss_out.entropy_loss,
            "reward_per_step": batch["reward"].mean(),
        }
        return params, opt_state, metrics

    return train_step


def make_lm_pretrain_step(cfg, opt, loss_chunk=512, grad_constraint=None,
                          mesh=None, rules=None):
    """Plain next-token-prediction step (substrate completeness: the data
    pipeline / LM pretraining driver; also the non-RL baseline).
    grad_constraint/mesh/rules as in ``make_lm_train_step`` (impls come
    from ``cfg.attn_impl``/``cfg.ssd_impl``) — ``--mode lm --mesh-data N
    --mesh-model M`` runs through the same 2-D mesh path."""
    mesh_ctx, shard_batch = _make_lm_mesh_fns(mesh, rules)

    def loss_fn(params, batch):
        tokens = batch["tokens"]          # (B, S+1)
        hidden, aux, _ = model_lib.forward(params, tokens[:, :-1], cfg=cfg,
                                           vision=batch.get("vision"))
        unembed = model_lib.unembed_matrix(params, cfg)
        loss = losses.chunked_softmax_xent(
            hidden, unembed, tokens[:, 1:], chunk=loss_chunk,
            final_softcap=cfg.final_logit_softcap)
        lb, zl, _ = aux
        return loss + cfg.router_aux_weight * lb + cfg.router_z_weight * zl, loss

    def train_step(params, opt_state, step, batch):
        with mesh_ctx():
            batch = shard_batch(batch)
            grads, xent = jax.grad(loss_fn, has_aux=True)(params, batch)
            if grad_constraint is not None:
                grads = grad_constraint(grads)
            updates, opt_state = opt.update(grads, opt_state, params, step)
            params = apply_updates(params, updates)
        return params, opt_state, {"loss": xent}

    return train_step
