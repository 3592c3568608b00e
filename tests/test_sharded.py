"""Data-parallel sharded learner tests (PR 3).

In-process (run on whatever devices the env has — 1 in the tier-1 suite,
8 in the sharded-cpu CI job):
  * mesh-size-1 is BIT-identical to the pre-change unsharded path (source
    stream, per-step losses, final params);
  * the Pallas V-trace kernel impl matches the scan impl in the
    learner-step metrics to 1e-5;
  * Runtime crash checkpointing, --resume/start_step, DeviceSource stop()
    state reset, windowed FPS.

Multi-device (subprocess under XLA_FLAGS=--xla_force_host_platform_
device_count=8, so it runs everywhere): mesh 1 vs 4 produce equal losses
on the same batches, and ShardedDeviceSource round-trips check_rollout.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from conftest import run_forced
from repro.configs.atari_impala import small_train
from repro.core import learner as learner_lib
from repro.core.runtime import Runtime
from repro.core.sources import (DeviceSource, ShardedDeviceSource,
                                check_rollout)
from repro.distributed.sharding import RL_AGENT_RULES, RULE_SETS, spec_for
from repro.envs import catch
from repro.launch.mesh import make_data_mesh
from repro.models.convnet import init_agent, minatar_net
from repro.optim import make_optimizer

T, B = 10, 8


def _agent():
    env = catch.make()
    init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
    return env, apply_fn, params


def _fixed_batch(env, seed=0, t=T, b=B):
    rng = np.random.default_rng(seed)
    return {
        "obs": jnp.asarray(rng.random((t + 1, b) + env.obs_shape),
                           jnp.float32),
        "action": jnp.asarray(rng.integers(0, env.num_actions, (t, b)),
                              jnp.int32),
        "behavior_logits": jnp.asarray(
            rng.normal(0, 1, (t, b, env.num_actions)), jnp.float32),
        "reward": jnp.asarray(rng.normal(0, 1, (t, b)), jnp.float32),
        "done": jnp.asarray(rng.random((t, b)) > 0.9),
    }


# ---------------------------------------------------------------------------
# rules table


def test_rl_agent_rules_replicate_params_shard_batch():
    assert RULE_SETS["rl_agent"] is RL_AGENT_RULES
    mesh = make_data_mesh(1)
    # every convnet/fc param axis replicated
    for axes in (("conv_h", "conv_w", "conv_in", "conv_out"),
                 ("fc_in", "fc_out")):
        assert spec_for(axes, mesh, RL_AGENT_RULES) == PartitionSpec()
    # activations shard their batch axis over the data axes
    assert spec_for(("act_batch",), mesh, RL_AGENT_RULES) == \
        PartitionSpec("data")


# ---------------------------------------------------------------------------
# mesh-size-1 bit-parity with the pre-change path


def test_sharded_source_mesh1_bit_identical_to_device_source():
    """Same key → the per-device fan-out at N=1 must reproduce the exact
    DeviceSource rollout stream (and obey the canonical contract)."""
    env, apply_fn, params = _agent()
    mesh = make_data_mesh(1)
    a = DeviceSource.for_env(env, apply_fn, unroll_length=T, batch_size=B,
                             key=jax.random.PRNGKey(3), pipelined=True)
    b = ShardedDeviceSource.for_env(env, apply_fn, unroll_length=T,
                                    batch_size=B, key=jax.random.PRNGKey(3),
                                    mesh=mesh, pipelined=True)
    assert b.frames_per_batch == a.frames_per_batch == T * B
    for _ in range(3):
        ra, rb = a.next_batch(params), b.next_batch(params)
        check_rollout(rb, T, B)
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), ra, rb)


def test_sharded_training_mesh1_bit_identical():
    """4 learner steps through the sharded path at mesh size 1 == the
    pre-change unsharded path, bit for bit (losses and final params)."""
    env, apply_fn, params0 = _agent()
    tc = small_train(unroll_length=T, batch_size=B, total_steps=50)
    opt = make_optimizer(tc)

    def run(mesh):
        src_kw = dict(unroll_length=T, batch_size=B,
                      key=jax.random.PRNGKey(1), pipelined=True)
        if mesh is None:
            source = DeviceSource.for_env(env, apply_fn, **src_kw)
            params = params0
        else:
            source = ShardedDeviceSource.for_env(env, apply_fn, mesh=mesh,
                                                 **src_kw)
            params = jax.device_put(
                params0, NamedSharding(mesh, PartitionSpec()))
        step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc,
                                                   mesh=mesh))
        opt_state = opt.init(params)
        losses = []
        for s in range(4):
            batch = source.next_batch(params)
            params, opt_state, m = step(params, opt_state, jnp.int32(s),
                                        batch)
            losses.append(float(m["loss"]))
        source.stop()
        return losses, params

    losses_a, params_a = run(None)
    losses_b, params_b = run(make_data_mesh(1))
    assert losses_a == losses_b
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), params_a, params_b)


# ---------------------------------------------------------------------------
# V-trace kernel impl on the learner hot path


def test_vtrace_kernel_impl_matches_scan_in_learner():
    """--vtrace-impl kernel: learner-step metrics match the scan impl to
    1e-5 (the kernel runs interpret-mode on CPU)."""
    env, apply_fn, params = _agent()
    tc = small_train(unroll_length=T, batch_size=B)
    opt = make_optimizer(tc)
    batch = _fixed_batch(env)
    out = {}
    for impl in ("scan", "kernel"):
        step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc,
                                                   vtrace_impl=impl))
        p, _, m = step(params, opt.init(params), jnp.int32(0), batch)
        out[impl] = (m, p)
    for k in ("loss", "pg_loss", "baseline_loss", "entropy_loss",
              "vs_mean", "rho_mean"):
        np.testing.assert_allclose(float(out["scan"][0][k]),
                                   float(out["kernel"][0][k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6),
        out["scan"][1], out["kernel"][1])


def test_vtrace_impl_rejects_unknown():
    from repro.core import losses
    with pytest.raises(ValueError):
        losses._vtrace_fn("fancy")


def test_vtrace_kernel_impl_matches_scan_logprob_path():
    """The LM-RL loss path (--mode lm-rl --vtrace-impl kernel) hits the
    kernel too: impala_loss_from_logprobs scan vs kernel to 1e-5."""
    from repro.core import losses
    rng = np.random.default_rng(0)
    args = dict(
        target_logprobs=jnp.asarray(rng.normal(-1.5, 0.3, (T, B)),
                                    jnp.float32),
        target_entropy=jnp.asarray(rng.random((T, B)), jnp.float32),
        behavior_logprobs=jnp.asarray(rng.normal(-1.5, 0.3, (T, B)),
                                      jnp.float32),
        rewards=jnp.asarray(rng.normal(0, 1, (T, B)), jnp.float32),
        discounts=jnp.asarray(rng.random((T, B)), jnp.float32),
        values=jnp.asarray(rng.normal(0, 1, (T, B)), jnp.float32),
        bootstrap_value=jnp.asarray(rng.normal(0, 1, (B,)), jnp.float32))
    a = losses.impala_loss_from_logprobs(**args, vtrace_impl="scan")
    b = losses.impala_loss_from_logprobs(**args, vtrace_impl="kernel")
    for k in ("total", "pg_loss", "baseline_loss", "vs_mean"):
        np.testing.assert_allclose(float(getattr(a, k)),
                                   float(getattr(b, k)),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# mesh 1 vs N parity + sharded contract (8 forced host devices, hermetic
# subprocess — conftest.run_forced — so it passes in the single-device
# tier-1 env too)

_PARITY_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.atari_impala import small_train
from repro.core import learner as L
from repro.core.sources import ShardedDeviceSource, check_rollout
from repro.distributed.sharding import RL_AGENT_RULES
from repro.envs import catch
from repro.launch.mesh import make_data_mesh
from repro.models.convnet import init_agent, minatar_net
from repro.optim import make_optimizer

T, B = 10, 8
env = catch.make()
tc = small_train(unroll_length=T, batch_size=B, total_steps=50)
init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
params0, _ = init_agent(init_fn, jax.random.PRNGKey(0))
opt = make_optimizer(tc)

rng = np.random.default_rng(0)
batches = []
for _ in range(3):
    batches.append({
        "obs": rng.random((T + 1, B) + env.obs_shape).astype(np.float32),
        "action": rng.integers(0, env.num_actions, (T, B)).astype(np.int32),
        "behavior_logits": rng.normal(
            0, 1, (T, B, env.num_actions)).astype(np.float32),
        "reward": rng.normal(0, 1, (T, B)).astype(np.float32),
        "done": rng.random((T, B)) > 0.9,
    })

def losses_on(n):
    mesh = make_data_mesh(n)
    step = jax.jit(L.make_train_step(apply_fn, opt, tc, mesh=mesh,
                                     rules=RL_AGENT_RULES))
    params = jax.device_put(params0, NamedSharding(mesh, PartitionSpec()))
    opt_state = opt.init(params)
    sharding = lambda nd: NamedSharding(  # noqa: E731
        mesh, PartitionSpec(*([None, "data"] + [None] * (nd - 2))))
    out = []
    for s, b in enumerate(batches):
        b = {k: jax.device_put(jnp.asarray(v), sharding(v.ndim))
             for k, v in b.items()}
        params, opt_state, m = step(params, opt_state, jnp.int32(s), b)
        out.append(float(m["loss"]))
    return out

l1, l4 = losses_on(1), losses_on(4)
print("mesh1", l1)
print("mesh4", l4)
np.testing.assert_allclose(l1, l4, rtol=1e-5, atol=1e-6)

# the sharded source fans 4 per-device streams into one global batch that
# round-trips the canonical contract, laid out over the mesh
mesh = make_data_mesh(4)
src = ShardedDeviceSource.for_env(env, apply_fn, unroll_length=T,
                                  batch_size=4 * B,
                                  key=jax.random.PRNGKey(1), mesh=mesh)
rollout = src.next_batch(params0)
check_rollout(rollout, T, 4 * B)
assert len(rollout["obs"].sharding.device_set) == 4
assert all(len(s.data.devices()) == 1
           for s in rollout["obs"].addressable_shards)
src.stop()

# sharded replay composes over the sharded source: per-device-sliced
# storage, mixed batch stays globally sharded (one shard per device, so
# no host concat / resharding entered the hot path), per-device
# interleaved is_replay mask, priorities route through (device, ticket)
from repro.core.sources import ReplaySource
from repro.core.replay import ShardedReplay
src = ShardedDeviceSource.for_env(env, apply_fn, unroll_length=T,
                                  batch_size=4 * B,
                                  key=jax.random.PRNGKey(2), mesh=mesh)
rs = ReplaySource(src, ShardedReplay("elite", 32, mesh), replay_ratio=1.0)
rs.start(params0)
for i in range(3):
    mixed = rs.next_batch(params0)
    check_rollout(mixed, T, 8 * B)
    assert len(mixed["obs"].sharding.device_set) == 4
    assert all(len(s.data.devices()) == 1
               for s in mixed["obs"].addressable_shards)
    mask = np.asarray(mixed["is_replay"])
    np.testing.assert_array_equal(
        mask, np.tile([False] * B + [True] * B, 4))
    rs.on_learner_metrics(i, {"priority": np.arange(8 * B,
                                                    dtype=np.float64)})
parts = rs.buffer._parts
assert all(len(p) > 0 for p in parts)
assert any((p._prio[p._live] != 1.0).any() for p in parts)
rs.stop()

# divisibility is enforced loudly
try:
    ShardedReplay("uniform", 30, mesh)
except ValueError as e:
    assert "not divisible" in str(e)
else:
    raise AssertionError("capacity 30 over 4 devices should fail")

# the host actor loop feeds the sharded learner: its stacked batch is
# split over the mesh data axis
from repro.core.sources import HostLoopSource
host = HostLoopSource(env, apply_fn, num_actors=4, unroll_length=T,
                      batch_size=4 * B, mesh=mesh)
try:
    host.start(params0)
    hr = host.next_batch(params0)
    check_rollout(hr, T, 4 * B)
    assert len(hr["obs"].sharding.device_set) == 4
finally:
    host.stop()
print("PARITY OK")
"""


def test_sharded_parity_mesh_1_vs_4_subprocess():
    proc = run_forced(script=_PARITY_SCRIPT, devices=8)
    assert "PARITY OK" in proc.stdout


# ---------------------------------------------------------------------------
# satellite fixes


class _CrashingSource:
    """Canonical source that blows up on the k-th batch (actor stall)."""

    def __init__(self, inner, crash_at):
        self.inner = inner
        self.crash_at = crash_at
        self.frames_per_batch = inner.frames_per_batch
        self.calls = 0

    def start(self, params):
        self.inner.start(params)

    def next_batch(self, params):
        if self.calls == self.crash_at:
            raise TimeoutError("actor stalled")
        self.calls += 1
        return self.inner.next_batch(params)

    def stop(self):
        self.inner.stop()


def test_runtime_crash_checkpoint_saves_progress(tmp_path):
    """A mid-training exception persists the last completed state (and
    re-raises); a second Runtime resumes from it at the saved step."""
    from repro import checkpoint as ckpt_lib
    env, apply_fn, params = _agent()
    tc = small_train(unroll_length=T, batch_size=B, total_steps=50)
    opt = make_optimizer(tc)
    src = _CrashingSource(
        DeviceSource.for_env(env, apply_fn, unroll_length=T, batch_size=B,
                             key=jax.random.PRNGKey(5), pipelined=False),
        crash_at=3)
    step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc))
    rt = Runtime(src, step, params, opt.init(params), total_steps=10,
                 log_every=0, checkpoint_dir=str(tmp_path),
                 print_fn=lambda s: None)
    with pytest.raises(TimeoutError):
        rt.run()
    path = ckpt_lib.latest_step_path(str(tmp_path))
    assert path is not None and os.path.basename(path) == "step_3"
    restored, meta = ckpt_lib.restore(
        path, {"params": params, "opt_state": opt.init(params)})
    assert meta["step"] == 3
    # the checkpoint carries the params of the last COMPLETED step
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), restored["params"], rt.params)

    # resume from it: the loop continues at step 3 (LR schedule intact)
    steps_seen = []
    src2 = DeviceSource.for_env(env, apply_fn, unroll_length=T,
                                batch_size=B, key=jax.random.PRNGKey(6))
    rt2 = Runtime(src2, step, restored["params"], restored["opt_state"],
                  total_steps=5, start_step=meta["step"], log_every=0,
                  on_metrics=lambda s, m: steps_seen.append(s),
                  print_fn=lambda s: None)
    rt2.run()
    assert steps_seen == [3, 4]


def test_runtime_crash_after_update_saves_next_step(tmp_path):
    """A failure AFTER the params update (e.g. in a metrics hook) must
    checkpoint step+1 — resuming must not re-apply the completed update."""
    from repro import checkpoint as ckpt_lib
    env, apply_fn, params = _agent()
    tc = small_train(unroll_length=T, batch_size=B, total_steps=50)
    opt = make_optimizer(tc)
    src = DeviceSource.for_env(env, apply_fn, unroll_length=T, batch_size=B,
                               key=jax.random.PRNGKey(5), pipelined=False)
    step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc))

    def boom(s, m):
        if s == 2:
            raise RuntimeError("metrics sink died")

    rt = Runtime(src, step, params, opt.init(params), total_steps=10,
                 log_every=0, checkpoint_dir=str(tmp_path), on_metrics=boom,
                 print_fn=lambda s: None)
    with pytest.raises(RuntimeError):
        rt.run()
    # update 2 IS in rt.params, so the checkpoint must say "run step 3 next"
    path = ckpt_lib.latest_step_path(str(tmp_path))
    assert os.path.basename(path) == "step_3"
    _, meta = ckpt_lib.restore(
        path, {"params": params, "opt_state": opt.init(params)})
    assert meta["step"] == 3


def test_runtime_no_crash_checkpoint_without_dir(tmp_path):
    env, apply_fn, params = _agent()
    tc = small_train(unroll_length=T, batch_size=B)
    opt = make_optimizer(tc)
    src = _CrashingSource(
        DeviceSource.for_env(env, apply_fn, unroll_length=T, batch_size=B,
                             key=jax.random.PRNGKey(5), pipelined=False),
        crash_at=0)
    step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc))
    rt = Runtime(src, step, params, opt.init(params), total_steps=4,
                 log_every=0, print_fn=lambda s: None)
    with pytest.raises(TimeoutError):
        rt.run()
    assert list(tmp_path.iterdir()) == []


def test_train_cli_resume_continues_from_saved_step(tmp_path, capsys):
    """Killed-and-resumed via the CLI: the second run restores
    {params, opt_state, step} and starts at the saved step, not 0."""
    from repro.launch import train as train_cli
    d = str(tmp_path)
    args = ["--mode", "rl-agent", "--env", "catch", "--batch", "8"]
    train_cli.main(args + ["--steps", "3", "--checkpoint-dir", d])
    assert os.path.exists(os.path.join(tmp_path, "step_3", "manifest.json"))
    capsys.readouterr()
    train_cli.main(args + ["--steps", "5", "--checkpoint-dir", d,
                           "--resume"])
    out = capsys.readouterr().out
    assert "resumed" in out and "at step 3" in out
    # the continued loop logs steps 3.. only — the schedule did not restart
    assert "step     3" in out and "step     0" not in out
    assert os.path.exists(os.path.join(tmp_path, "step_5", "manifest.json"))


def test_runtime_resume_past_end_writes_no_relabeled_checkpoint(tmp_path):
    """--resume --steps N with a saved step >= N runs nothing and must NOT
    relabel the restored state with a smaller step number."""
    env, apply_fn, params = _agent()
    tc = small_train(unroll_length=T, batch_size=B)
    opt = make_optimizer(tc)
    src = DeviceSource.for_env(env, apply_fn, unroll_length=T, batch_size=B,
                               key=jax.random.PRNGKey(5))
    step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc))
    rt = Runtime(src, step, params, opt.init(params), total_steps=3,
                 start_step=5, log_every=0, checkpoint_dir=str(tmp_path),
                 print_fn=lambda s: None)
    rt.run()
    assert list(tmp_path.iterdir()) == []


def test_device_source_stop_resets_dispatch_state():
    """Stale-restart fix: after stop(), a restarted source with
    param_sync_every > 1 must act with the NEW params, not last run's."""
    env, apply_fn, params = _agent()
    newer = jax.tree.map(lambda x: x + 1.0, params)
    for make in (
        lambda: DeviceSource.for_env(
            env, apply_fn, unroll_length=T, batch_size=B,
            key=jax.random.PRNGKey(4), pipelined=False,
            param_sync_every=2),
        lambda: ShardedDeviceSource.for_env(
            env, apply_fn, unroll_length=T, batch_size=B,
            key=jax.random.PRNGKey(4), mesh=make_data_mesh(1),
            pipelined=False, param_sync_every=2),
    ):
        src = make()
        src.start(params)
        src.next_batch(params)     # dispatch 0: behavior <- params
        src.stop()
        assert src._behavior_params is None and src._dispatches == 0
        src.start(newer)
        src.next_batch(newer)      # dispatch 0 of the NEW run: resync
        held = src._behavior_params
        held_leaf = jax.tree.leaves(
            held[0] if isinstance(held, list) else held)[0]
        np.testing.assert_array_equal(np.asarray(held_leaf),
                                      np.asarray(jax.tree.leaves(newer)[0]))


def test_windowed_fps_reflects_recent_rate(monkeypatch):
    """The fps column is windowed (since the previous log line); the
    lifetime average moves to fps_avg — a late slowdown must show up."""
    import repro.core.runtime as runtime_mod

    class _Src:
        frames_per_batch = 100

        def start(self, p):
            pass

        def next_batch(self, p):
            return None

        def stop(self):
            pass

    rt = Runtime(_Src(), lambda p, o, s, b: (p, o, {}), None, None,
                 total_steps=10, log_every=1)
    lines = []
    rt.print_fn = lines.append
    rt.metrics = {}
    clock = iter([0.0, 1.0, 2.0])  # t0, first _log, second _log
    monkeypatch.setattr(runtime_mod.time, "monotonic", lambda: next(clock))
    t0 = runtime_mod.time.monotonic()
    rt._win_t, rt._win_frames = t0, 0
    rt.frames = 1000
    rt._log(0, t0)                 # 1000 frames in 1s
    rt.frames = 1100
    rt._log(1, t0)                 # only 100 frames in the last second
    assert "fps=1000" in lines[0] and "fps_avg=1000" in lines[0]
    assert "fps=100 " in lines[1] + " "
    assert "fps_avg=550" in lines[1]
