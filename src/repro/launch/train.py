"""End-to-end training driver: thin config -> Runtime assembly.

Every mode builds (RolloutSource, step_fn) and hands them to the unified
``core.runtime.Runtime`` — there is no per-mode step loop here.

Modes:
  rl-agent  — paper-faithful IMPALA: on-device rollouts (catch/gridworld
              envs) + convnet agent + V-trace learner, double-buffered by
              default (``--sync`` to disable, ``--actors host`` for the
              MonoBeast/PolyBeast host-loop actor architecture).
  lm-rl     — IMPALA with an LLM policy on the token-MDP: the decode path
              generates episodes (behavior log-probs recorded), the learner
              applies V-trace (DESIGN.md §2).
  lm        — plain next-token pretraining on the synthetic corpus.

Meshes: rl-agent shards over a 1-D ("data",) mesh (--mesh-data); the LM
paths shard over a 2-D ("data","model") mesh (--mesh-data x --mesh-model,
MEGATRON_RULES: params over "model", token batch over "data") and run
multi-host via --coordinator/--num-processes/--process-id (the
jax.distributed bootstrap of launch/multihost.py — the mesh is built from
the GLOBAL device set, so the same entry point runs single-host CPU CI
and a real pod slice).

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode rl-agent --env catch \
      --steps 500
  PYTHONPATH=src python -m repro.launch.train --mode rl-agent --actors host \
      --steps 50
  PYTHONPATH=src python -m repro.launch.train --mode rl-agent --env catch \
      --replay elite --replay-ratio 1.0 --steps 500
  PYTHONPATH=src python -m repro.launch.train --mode lm-rl \
      --arch granite-moe-1b-a400m --reduced --steps 50
  PYTHONPATH=src python -m repro.launch.train --mode lm --arch qwen3-4b \
      --reduced --steps 100 --checkpoint-dir /tmp/ckpt
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --mode lm-rl --arch qwen3-4b --reduced \
      --steps 50 --mesh-data 2 --mesh-model 2
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced_config
from repro.configs.atari_impala import small_train
from repro.configs.base import ImplContext, TrainConfig
from repro.core import learner as learner_lib
from repro.core import sources as sources_lib
from repro.core.runtime import Runtime
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as model_lib
from repro.models.convnet import impala_deep, init_agent, minatar_net
from repro.optim import make_optimizer


def build_rl_agent(args):
    import dataclasses

    from repro.envs import catch, gridworld
    env = {"catch": catch, "gridworld": gridworld}[args.env].make()
    train_cfg = small_train(total_steps=args.steps,
                            learning_rate=args.lr or 2e-3,
                            batch_size=args.batch or 32)
    if args.replay != "off":
        train_cfg = dataclasses.replace(train_cfg, clear_policy_cost=0.01,
                                        clear_value_cost=0.005)
    net = impala_deep if args.agent == "deep" else minatar_net
    init_fn, apply_fn = net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(train_cfg.seed))
    opt = make_optimizer(train_cfg)

    # The source composition matrix: (device | sharded | host) actors,
    # optionally wrapped in replay — every combination with --mesh-data
    # composes (per-device-sliced replay, mesh-split host learner queue).
    mesh = None
    if args.mesh_data:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(args.mesh_data)

    if args.actors == "host":
        source = sources_lib.HostLoopSource(
            env, apply_fn, num_actors=train_cfg.num_actors,
            unroll_length=train_cfg.unroll_length,
            batch_size=train_cfg.batch_size, seed=train_cfg.seed,
            mesh=mesh)
    elif mesh is not None:
        source = sources_lib.ShardedDeviceSource.for_env(
            env, apply_fn, unroll_length=train_cfg.unroll_length,
            batch_size=train_cfg.batch_size,
            key=jax.random.PRNGKey(train_cfg.seed + 1),
            mesh=mesh, pipelined=not args.sync)
    else:
        source = sources_lib.DeviceSource.for_env(
            env, apply_fn, unroll_length=train_cfg.unroll_length,
            batch_size=train_cfg.batch_size,
            key=jax.random.PRNGKey(train_cfg.seed + 1),
            pipelined=not args.sync)
    if args.replay != "off":
        from repro.core import replay as replay_lib
        if mesh is not None:
            buffer = replay_lib.ShardedReplay(args.replay,
                                              args.replay_capacity, mesh)
        else:
            buffer = replay_lib.make_buffer(args.replay,
                                            args.replay_capacity)
        source = sources_lib.ReplaySource(
            source, buffer,
            replay_ratio=args.replay_ratio, seed=train_cfg.seed,
            value_fn=jax.jit(lambda p, obs: apply_fn(p, obs).baseline))
    step_fn = jax.jit(learner_lib.make_train_step(
        apply_fn, opt, train_cfg, mesh=mesh,
        vtrace_impl=args.vtrace_impl))
    extras = {"log_keys": ("reward_per_step", "loss")}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        # learner state lives replicated on the mesh; the source reads
        # per-device shard views of it with zero copies.
        placement = lambda tree: jax.device_put(  # noqa: E731
            tree, NamedSharding(mesh, PartitionSpec()))
        params = placement(params)
        extras["placement"] = placement
    return source, step_fn, params, opt.init(params), extras


def _lm_mesh_setup(args, params, axes):
    """2-D ("data","model") mesh context for the LM paths: place the
    params per MEGATRON_RULES (model-sharded where divisible; the token
    batch shards over "data" inside the learner step) and build the
    grad-constraint hook pinning gradients to the same layout. Returns
    (mesh, rules, placed_params, grad_constraint) — (None, None, params,
    None) when neither --mesh-data nor --mesh-model is set, which
    compiles to the exact pre-mesh program."""
    if not (args.mesh_data or args.mesh_model):
        return None, None, params, None
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh2d
    mesh = make_mesh2d(args.mesh_data or 1, args.mesh_model or 1)
    rules = shd.MEGATRON_RULES
    pshard = shd.param_shardings(axes, mesh, rules, params)
    params = jax.device_put(params, pshard)
    grad_constraint = lambda grads: jax.tree.map(  # noqa: E731
        jax.lax.with_sharding_constraint, grads, pshard)
    return mesh, rules, params, grad_constraint


def _restore_shardings(params, opt_state):
    """extras entry telling --resume to reassemble each restored leaf onto
    the LIVE mesh layout (checkpoint.restore ``shardings=``). Because the
    shardings come from the freshly-initialised state — not the
    checkpoint — this is also the elastic-resume path: a checkpoint from
    mesh (2,2) restores onto (4,1) by re-slicing the saved shards."""
    from repro.distributed.sharding import tree_shardings
    return tree_shardings({"params": params, "opt_state": opt_state})


def _apply_impls(cfg, args):
    """Fold --attn-impl / --ssd-impl into the model config (the single
    ImplContext every downstream path reads, mirroring --vtrace-impl)."""
    return ImplContext.from_args(args).apply(cfg)


def build_lm_rl(args):
    cfg = _apply_impls(
        (get_reduced_config if args.reduced else get_config)(args.arch), args)
    train_cfg = TrainConfig(optimizer="adamw", learning_rate=args.lr or 3e-4,
                            grad_clip=1.0, total_steps=args.steps,
                            lr_schedule="constant", entropy_cost=0.003)
    params, axes = model_lib.init(jax.random.PRNGKey(train_cfg.seed), cfg)
    opt = make_optimizer(train_cfg)
    mesh, rules, params, grad_constraint = _lm_mesh_setup(args, params, axes)
    opt_state = opt.init(params)   # zeros_like inherits the param shardings
    source = sources_lib.GeneratorSource(
        cfg, batch_size=args.batch or 16, episode_length=args.seq,
        key=jax.random.PRNGKey(7), mesh=mesh, rules=rules)
    step_fn = jax.jit(sources_lib.lm_rl_step_from_rollout(
        learner_lib.make_lm_train_step(cfg, opt, train_cfg,
                                       loss_chunk=args.seq,
                                       vtrace_impl=args.vtrace_impl,
                                       grad_constraint=grad_constraint,
                                       mesh=mesh, rules=rules)))
    extras = {"log_keys": ("reward_per_step", "pg_loss", "entropy_loss")}
    if mesh is not None:
        extras["restore_shardings"] = _restore_shardings(params, opt_state)
    return source, step_fn, params, opt_state, extras


def build_lm(args):
    from repro.data import PackedBatchIterator, markov_corpus
    cfg = _apply_impls(
        (get_reduced_config if args.reduced else get_config)(args.arch), args)
    train_cfg = TrainConfig(optimizer="adamw", learning_rate=args.lr or 3e-4,
                            grad_clip=1.0, total_steps=args.steps,
                            lr_schedule="cosine", warmup_steps=10)
    params, axes = model_lib.init(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(train_cfg)
    mesh, rules, params, grad_constraint = _lm_mesh_setup(args, params, axes)
    opt_state = opt.init(params)
    step_fn = jax.jit(learner_lib.make_lm_pretrain_step(
        cfg, opt, loss_chunk=min(512, args.seq),
        grad_constraint=grad_constraint, mesh=mesh, rules=rules))

    b = args.batch or 16
    corpus = markov_corpus(cfg.vocab_size, 200_000, seed=1)
    # Checkpointable iterator (seed + offset): its state rides in every
    # checkpoint through DataSource.state_dict, so --resume replays the
    # exact batch sequence (bit-identical to an uninterrupted run).
    it = PackedBatchIterator(corpus, b, args.seq, seed=train_cfg.seed)
    vision = None
    if cfg.vision_seq:
        vision = jnp.zeros((b, cfg.vision_seq, cfg.d_model),
                           jnp.dtype(cfg.dtype))
    put = jnp.asarray
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.distributed.sharding import batch_axes_spec
        put = lambda v: jax.device_put(v, NamedSharding(  # noqa: E731
            mesh, batch_axes_spec(mesh, rules, v.ndim, v.shape, 0)
            or PartitionSpec()))

    def transform(batch):
        batch = {k: put(v) for k, v in batch.items()}
        if vision is not None:
            batch["vision"] = vision
        return batch

    source = sources_lib.DataSource(it, frames_per_batch=b * args.seq,
                                    transform=transform, close=it.close)
    extras = {"log_keys": ("loss",), "fps_label": "tok/s"}
    if mesh is not None:
        extras["restore_shardings"] = _restore_shardings(params, opt_state)
    return source, step_fn, params, opt_state, extras


_BUILDERS = {"rl-agent": build_rl_agent, "lm-rl": build_lm_rl,
             "lm": build_lm}


def _checkpoint_meta(args):
    """Config identity recorded in every checkpoint manifest and validated
    on --resume: restoring an lm checkpoint into an rl-agent run (or a
    different arch/env) must fail loudly up front, naming the mismatched
    keys — not die deep in tree-structure assembly."""
    meta = {"mode": args.mode}
    if args.mode == "rl-agent":
        meta["env"] = args.env
    else:
        meta["arch"] = args.arch
    return meta


def main(argv=None):
    """Parse ``argv``, build the mode's source and learner step, and run
    them; returns the finished ``Runtime`` (its ``params``, ``metrics``
    and ``source``)."""
    use_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=sorted(_BUILDERS), default="rl-agent")
    p.add_argument("--env", choices=["catch", "gridworld"], default="catch")
    p.add_argument("--agent", choices=["minatar", "deep"], default="minatar")
    p.add_argument("--actors", choices=["device", "host"], default="device",
                   help="rl-agent only: compiled on-device rollouts or the "
                        "MonoBeast host actor loop")
    p.add_argument("--sync", action="store_true",
                   help="disable double-buffered rollout dispatch")
    p.add_argument("--mesh-data", type=int, default=None, metavar="N",
                   help="data-parallel axis size: rl-agent shards batch + "
                        "source over a 1-D ('data',) mesh "
                        "(ShardedDeviceSource + sharded train step); "
                        "lm/lm-rl use it as the 'data' axis of the 2-D "
                        "('data','model') mesh (on CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--mesh-model", type=int, default=None, metavar="M",
                   help="lm/lm-rl only: model-parallel axis size of the "
                        "2-D ('data','model') mesh — MEGATRON_RULES shard "
                        "params/activations over 'model' and the token "
                        "batch over 'data'; composes with --mesh-data "
                        "and --resume")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host: address of process 0 "
                        "(jax.distributed bootstrap, launch/multihost.py); "
                        "the mesh is then built from the GLOBAL device set")
    p.add_argument("--num-processes", type=int, default=1,
                   help="multi-host: total process count")
    p.add_argument("--process-id", type=int, default=0,
                   help="multi-host: this process's index")
    p.add_argument("--vtrace-impl", choices=["scan", "kernel"],
                   default="scan",
                   help="rl-agent/lm-rl: V-trace recursion — reverse-scan "
                        "reference or the Pallas TPU kernel "
                        "(interpret-mode on CPU); ignored by --mode lm")
    p.add_argument("--attn-impl", default=None,
                   choices=["xla", "xla_chunked", "xla_chunked_skip",
                            "kernel"],
                   help="lm/lm-rl: attention impl on every hot path — "
                        "'kernel' selects the Pallas flash-attention "
                        "kernel for train/prefill and the decode-attention "
                        "kernel for generation (interpret-mode on CPU); "
                        "default: the config's attn_impl ('auto')")
    p.add_argument("--ssd-impl", default=None, choices=["xla", "kernel"],
                   help="lm/lm-rl: Mamba2 chunked-scan impl — 'kernel' "
                        "routes each SSD chunk to the Pallas kernel "
                        "(skips the (L,L) decay-matrix materialisation); "
                        "default: the config's ssd_impl ('xla')")
    p.add_argument("--resume", action="store_true",
                   help="restore {params, opt_state, step} AND the rollout "
                        "source state (env carries, RNG streams, replay "
                        "contents) from the latest checkpoint in "
                        "--checkpoint-dir and continue from the saved step "
                        "— bit-identical to an uninterrupted run for the "
                        "on-device actor paths")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="also checkpoint every N steps (0: final/crash "
                        "checkpoints only) — the kill/--resume safety net")
    p.add_argument("--replay", default="off",
                   choices=["off", "uniform", "elite", "attentive"],
                   help="rl-agent only: mix replayed rollouts into every "
                        "learner batch (core/replay.py)")
    p.add_argument("--replay-capacity", type=int, default=512,
                   help="replay buffer size in rollouts")
    p.add_argument("--replay-ratio", type=float, default=1.0,
                   help="replayed:fresh columns per batch (1.0 = 1:1)")
    p.add_argument("--arch", default="qwen3-4b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--checkpoint-dir", default=None)
    args = p.parse_args(argv)
    if args.mesh_model and args.mode == "rl-agent":
        p.error("--mesh-model applies to the LM paths (--mode lm/lm-rl); "
                "rl-agent is data-parallel only (--mesh-data)")
    if args.num_processes > 1 and not args.coordinator:
        # without the bootstrap each process would train a full
        # independent model and clobber the shared checkpoint dir
        p.error("--num-processes > 1 requires --coordinator")
    if args.coordinator:
        # must run before the builders query devices: the mesh factories
        # read jax.devices(), which is global only after the bootstrap.
        from repro.launch.multihost import bootstrap
        bootstrap(args.coordinator, args.num_processes, args.process_id)

    source, step_fn, params, opt_state, extras = _BUILDERS[args.mode](args)
    placement = extras.pop("placement", None)
    restore_shardings = extras.pop("restore_shardings", None)
    start_step = 0
    if args.resume:
        if not args.checkpoint_dir:
            p.error("--resume requires --checkpoint-dir")
        from repro import checkpoint as ckpt_lib
        path = ckpt_lib.latest_step_path(args.checkpoint_dir)
        if path is None:
            print(f"--resume: no checkpoint under {args.checkpoint_dir}, "
                  "starting fresh")
        else:
            # Cheap pre-flight: the manifest's recorded config identity
            # must match this run before any shard is read.
            saved_meta = ckpt_lib.read_metadata(path)
            want = _checkpoint_meta(args)
            bad = sorted(k for k in want
                         if k in saved_meta and saved_meta[k] != want[k])
            if bad:
                detail = ", ".join(
                    f"{k}: checkpoint={saved_meta[k]!r} run={want[k]!r}"
                    for k in bad)
                raise SystemExit(
                    f"--resume: checkpoint {path} was written by a "
                    f"different configuration ({detail})")
            # sharded-aware restore: with restore_shardings each leaf is
            # reassembled straight onto its live mesh sharding
            # (model-sharded params land distributed, no replicated host
            # tree) — including elastic resume onto a different mesh.
            # Same-mesh, prefer the SAVED specs (bit-exact resume: the
            # resumed step then compiles the exact steady-state program).
            if restore_shardings is not None:
                restore_shardings = (ckpt_lib.saved_shardings(
                    path, restore_shardings) or restore_shardings)
            restored, meta = ckpt_lib.restore(
                path, {"params": params, "opt_state": opt_state},
                shardings=restore_shardings)
            if restore_shardings is not None:
                params = restored["params"]
                opt_state = restored["opt_state"]
            else:
                place = placement or (
                    lambda tree: jax.tree.map(jnp.asarray, tree))
                params = place(restored["params"])
                opt_state = place(restored["opt_state"])
            start_step = int(meta.get("step", 0))
            # SourceState: replay the exact rollout stream (env carries,
            # RNG, replay slots). Checkpoints from before the protocol
            # restore learner state only (source starts fresh).
            source_state = ckpt_lib.restore_structured(path, "source")
            if source_state is not None:
                source.load_state_dict(source_state)
            print(f"resumed {path} at step {start_step}"
                  + (" (source state restored)"
                     if source_state is not None else ""))
    runtime = Runtime(source, step_fn, params, opt_state,
                      total_steps=args.steps, start_step=start_step,
                      checkpoint_dir=args.checkpoint_dir,
                      checkpoint_every=args.checkpoint_every,
                      checkpoint_meta=_checkpoint_meta(args), **extras)
    runtime.run()
    return runtime


if __name__ == "__main__":
    main()
