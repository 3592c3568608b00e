"""Benchmark harness — one function per paper table/claim plus the
roofline-table generator. Prints ``name,us_per_call,derived`` CSV rows and
writes each suite's rows to ``BENCH_<suite>.json`` (the CI bench-smoke
artifact, so the perf trajectory is captured per-PR).

Paper analogues:
  fps_host_loop     — PolyBeast throughput (frames/s): DynamicBatcher +
                      actor threads + learner queue (the §4 FPS claim).
  fps_on_device     — the TPU-native (Anakin) rollout+learn step FPS.
  learner_step      — batched IMPALA learner step latency.
  vtrace            — V-trace computation (scan and Pallas-interpret paths).
  pipeline          — sync vs double-buffered rollout-learn overlap FPS.
  scaling           — data-parallel sharded learner FPS vs mesh size
                      (1/2/4/8 devices; forces 8 host CPU devices via
                      XLA_FLAGS when requested).
  replay            — off-policy replay (core/replay.py): FPS + frames to
                      the catch solve threshold for replay off/uniform/
                      elite at a 1:1 replay ratio, and gridworld return at
                      a fixed frame budget.
  attention         — chunked-vs-dense attention latency (model path).
  kernels           — xla vs Pallas kernel per hot-path op (flash/decode/
                      ssd/vtrace) with achieved-vs-roofline accounting.
  dynamic_batcher   — batching overhead per request.
  generate          — serving decode throughput (tokens/s).
  roofline_table    — re-prints the dry-run roofline terms per (arch, shape)
                      from experiments/dryrun (run launch.dryrun first).

``--suite`` may be given multiple times (``--suite pipeline --suite
replay``); ``--small`` shrinks every suite to CI-smoke scale.
"""

from __future__ import annotations

import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

SMALL = False        # set by --small: CI-smoke scale
_RESULTS = []        # rows of the suite currently running (JSON artifact)


def row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)
    _RESULTS.append({"name": name, "us_per_call": round(us, 1),
                     "derived": derived})


def timeit(fn, n=20, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------

def bench_vtrace():
    from repro.core.vtrace import vtrace_from_importance_weights
    from repro.kernels import ops
    t, b = 80, 256
    rng = np.random.default_rng(0)
    args = [jnp.asarray(rng.normal(0, 1, (t, b)), jnp.float32)
            for _ in range(4)] + [jnp.asarray(rng.normal(0, 1, (b,)),
                                              jnp.float32)]
    f = jax.jit(vtrace_from_importance_weights)
    us = timeit(lambda: jax.block_until_ready(f(*args)))
    row("vtrace_scan_T80_B256", us, f"{t*b/us:.1f}steps/us")

    g = jax.jit(ops.vtrace_from_importance_weights_kernel)
    us = timeit(lambda: jax.block_until_ready(g(*args)), n=3)
    row("vtrace_pallas_interp_T80_B256", us, "interpret-mode")


def bench_learner_step():
    from repro.configs.atari_impala import small_train
    from repro.core import learner as L
    from repro.envs import catch
    from repro.models.convnet import init_agent, minatar_net
    from repro.optim import make_optimizer
    env = catch.make()
    tc = small_train(unroll_length=20, batch_size=32)
    init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
    opt = make_optimizer(tc)
    opt_state = opt.init(params)
    step = jax.jit(L.make_train_step(apply_fn, opt, tc))
    rng = np.random.default_rng(0)
    t, b = tc.unroll_length, tc.batch_size
    batch = {
        "obs": jnp.asarray(rng.random((t + 1, b) + env.obs_shape),
                           jnp.float32),
        "action": jnp.asarray(rng.integers(0, 3, (t, b)), jnp.int32),
        "behavior_logits": jnp.asarray(rng.normal(0, 1, (t, b, 3)),
                                       jnp.float32),
        "reward": jnp.asarray(rng.normal(0, 1, (t, b)), jnp.float32),
        "done": jnp.asarray(rng.random((t, b)) > 0.9),
    }
    us = timeit(lambda: jax.block_until_ready(
        step(params, opt_state, jnp.int32(0), batch)[2]["loss"]))
    row("learner_step_T20_B32", us, f"{t*b/(us/1e6):.0f}fps")


def bench_fps_on_device(steps=30):
    """Compiled rollout+learn (the PolyBeast->TPU adaptation)."""
    from repro.configs.atari_impala import small_train
    from repro.core import learner as L, rollout as R
    from repro.envs import catch
    from repro.models.convnet import init_agent, minatar_net
    from repro.optim import make_optimizer
    env = catch.make()
    tc = small_train(unroll_length=20, batch_size=32)
    init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
    opt = make_optimizer(tc)
    opt_state = opt.init(params)
    key = jax.random.PRNGKey(1)
    carry = R.env_reset_batch(env, key, tc.batch_size)
    unroll = R.make_unroll(env, apply_fn, tc.unroll_length)
    train_step = L.make_train_step(apply_fn, opt, tc)

    @jax.jit
    def combined(params, opt_state, step, carry, key):
        carry, ro = unroll(params, carry, key)
        params, opt_state, m = train_step(params, opt_state, step, ro)
        return params, opt_state, carry, m

    params, opt_state, carry, _ = combined(params, opt_state, jnp.int32(0),
                                           carry, key)
    t0 = time.perf_counter()
    m = None
    for s in range(steps):
        key, k = jax.random.split(key)
        params, opt_state, carry, m = combined(
            params, opt_state, jnp.int32(s), carry, k)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    frames = steps * tc.batch_size * tc.unroll_length
    row("fps_on_device_catch", dt / steps * 1e6, f"{frames/dt:.0f}fps")


def bench_pipeline(steps=60, repeats=3):
    """Synchronous vs double-buffered rollout-learn overlap (the Runtime's
    pipelined DeviceSource): same unroll + learner step, with and without
    one-step-lag double buffering."""
    if SMALL:
        steps, repeats = 20, 1
    from repro.configs.atari_impala import small_train
    from repro.core import learner as L
    from repro.core.sources import DeviceSource
    from repro.envs import catch, gridworld
    from repro.models.convnet import init_agent, minatar_net
    from repro.optim import make_optimizer

    for env_name, env_mod in (("catch", catch), ("gridworld", gridworld)):
        env = env_mod.make()
        tc = small_train(unroll_length=20, batch_size=32)
        init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
        params0, _ = init_agent(init_fn, jax.random.PRNGKey(0))
        opt = make_optimizer(tc)
        step_fn = jax.jit(L.make_train_step(apply_fn, opt, tc))
        fps = {}
        for pipelined in (False, True):
            best = 0.0
            for rep in range(repeats):
                source = DeviceSource.for_env(
                    env, apply_fn, unroll_length=tc.unroll_length,
                    batch_size=tc.batch_size, key=jax.random.PRNGKey(1),
                    pipelined=pipelined)
                params, opt_state = params0, opt.init(params0)
                m = None
                for s in range(5):  # warmup: compile unroll + learner step
                    batch = source.next_batch(params)
                    params, opt_state, m = step_fn(params, opt_state,
                                                   jnp.int32(s), batch)
                jax.block_until_ready(m["loss"])
                t0 = time.perf_counter()
                for s in range(steps):
                    batch = source.next_batch(params)
                    params, opt_state, m = step_fn(
                        params, opt_state, jnp.int32(5 + s), batch)
                jax.block_until_ready(m["loss"])
                dt = time.perf_counter() - t0
                best = max(best, steps * source.frames_per_batch / dt)
            mode = "pipelined" if pipelined else "sync"
            fps[mode] = best
            row(f"pipeline_{mode}_{env_name}",
                steps * tc.unroll_length * tc.batch_size / best * 1e6 / steps,
                f"{best:.0f}fps")
        row(f"pipeline_speedup_{env_name}", 0.0,
            f"{fps['pipelined'] / fps['sync']:.3f}x")


def _train_catch(mode, *, steps, threshold=0.05, window=50, seed=0,
                 replay_ratio=1.0, capacity=256, env_name="catch"):
    """One replay arm: train on catch (or gridworld), tracking the running
    mean of reward_per_step. Returns (fps over fresh env frames,
    frames at which the threshold was first sustained or None,
    final running-mean reward, fresh frames per batch)."""
    import collections
    import dataclasses

    from repro.configs.atari_impala import small_train
    from repro.core import learner as L
    from repro.core import replay as replay_lib
    from repro.core.sources import DeviceSource, ReplaySource
    from repro.envs import catch, gridworld

    env = {"catch": catch, "gridworld": gridworld}[env_name].make()
    tc = small_train(unroll_length=20, batch_size=32, learning_rate=2e-3,
                     total_steps=steps)
    if mode != "off":
        tc = dataclasses.replace(tc, clear_policy_cost=0.01,
                                 clear_value_cost=0.005)
    from repro.models.convnet import init_agent, minatar_net
    init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(seed))
    from repro.optim import make_optimizer
    opt = make_optimizer(tc)
    opt_state = opt.init(params)
    step_fn = jax.jit(L.make_train_step(apply_fn, opt, tc))

    source = DeviceSource.for_env(
        env, apply_fn, unroll_length=tc.unroll_length,
        batch_size=tc.batch_size, key=jax.random.PRNGKey(seed + 1),
        pipelined=True)
    if mode != "off":
        source = ReplaySource(source, replay_lib.make_buffer(mode, capacity),
                              replay_ratio=replay_ratio, seed=seed,
                              value_fn=jax.jit(
                                  lambda p, obs: apply_fn(p, obs).baseline))
    feedback = getattr(source, "on_learner_metrics", None)

    rewards = collections.deque(maxlen=window)
    solved_frames = None
    source.start(params)
    try:
        # one step outside the clock to absorb compilation
        batch = source.next_batch(params)
        params, opt_state, m = step_fn(params, opt_state, jnp.int32(0),
                                       batch)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for s in range(1, steps):
            batch = source.next_batch(params)
            params, opt_state, m = step_fn(params, opt_state, jnp.int32(s),
                                           batch)
            if feedback is not None:
                feedback(s, m)
            rewards.append(float(m["reward_per_step"]))
            if (solved_frames is None and len(rewards) == window
                    and np.mean(rewards) >= threshold):
                solved_frames = (s + 1) * source.frames_per_batch
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
    finally:
        source.stop()
    fps = (steps - 1) * source.frames_per_batch / dt
    return (fps, solved_frames,
            float(np.mean(rewards)) if rewards else 0.0,
            source.frames_per_batch)


def bench_replay():
    """Off-policy replay on vs off: fresh-frame FPS and frames to the catch
    solve threshold (running-mean reward/step >= 0.05 over 50 steps;
    optimum is +0.1) for replay off / uniform / elite at replay_ratio 1:1,
    plus gridworld return at a fixed fresh-frame budget."""
    steps = 60 if SMALL else 1000
    window = 10 if SMALL else 50
    for mode in ("off", "uniform", "elite"):
        fps, solved, final, fpb = _train_catch(mode, steps=steps,
                                               window=window)
        solved_s = str(solved) if solved is not None else "never"
        row(f"replay_{mode}_catch", 1e6 / fps * fpb,
            f"{fps:.0f}fps solve_frames={solved_s} "
            f"final_reward={final:+.3f}")
    grid_steps = 30 if SMALL else 300
    for mode in ("off", "elite"):
        fps, _, final, fpb = _train_catch(mode, steps=grid_steps,
                                          window=window,
                                          threshold=float("inf"),
                                          env_name="gridworld")
        row(f"replay_{mode}_gridworld", 1e6 / fps * fpb,
            f"{fps:.0f}fps return_at_budget={final:+.3f}")


def bench_scaling(steps=40):
    """Data-parallel learner scaling: rollout+learn FPS vs mesh size for
    1/2/4/8 devices (weak scaling: 32 batch columns per device), plain and
    composed with the per-device-sliced replay buffer (``scaling_replay_*``
    rows — the sharded+replay FPS must stay close to sharded-only: the
    composition adds slot bookkeeping, not host-side concat/resharding).
    On CPU run under XLA_FLAGS=--xla_force_host_platform_device_count=8 —
    ``main`` sets it automatically when scaling is the SOLE suite requested
    (mixing it with other suites would skew their timings); otherwise the
    curve is truncated to the visible device count."""
    if SMALL:
        steps = 12
    from repro.configs.atari_impala import small_train
    from repro.core import learner as L
    from repro.core.replay import ShardedReplay
    from repro.core.sources import ReplaySource, ShardedDeviceSource
    from repro.distributed.sharding import RL_AGENT_RULES
    from repro.envs import catch
    from repro.launch.mesh import make_data_mesh
    from repro.models.convnet import init_agent, minatar_net
    from repro.optim import make_optimizer
    from jax.sharding import NamedSharding, PartitionSpec

    env = catch.make()
    n_dev = len(jax.devices())
    counts = [n for n in (1, 2, 4, 8) if n <= n_dev]
    per_device_batch = 32

    def arm(n, replay):
        mesh = make_data_mesh(n)
        tc = small_train(unroll_length=20, batch_size=per_device_batch * n)
        init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
        params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        opt = make_optimizer(tc)
        opt_state = opt.init(params)
        step_fn = jax.jit(L.make_train_step(apply_fn, opt, tc, mesh=mesh,
                                            rules=RL_AGENT_RULES))
        source = ShardedDeviceSource.for_env(
            env, apply_fn, unroll_length=tc.unroll_length,
            batch_size=tc.batch_size, key=jax.random.PRNGKey(1), mesh=mesh,
            pipelined=True)
        if replay:
            source = ReplaySource(
                source, ShardedReplay("uniform", 16 * n, mesh),
                replay_ratio=0.25)
        m = None
        for s in range(4):  # warmup: compile per-device unrolls + step
            batch = source.next_batch(params)
            params, opt_state, m = step_fn(params, opt_state, jnp.int32(s),
                                           batch)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for s in range(steps):
            batch = source.next_batch(params)
            params, opt_state, m = step_fn(params, opt_state,
                                           jnp.int32(4 + s), batch)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
        source.stop()
        fps = steps * source.frames_per_batch / dt
        return fps, dt, tc.batch_size

    for n in counts:
        fps, dt, bsz = arm(n, replay=False)
        row(f"scaling_n{n}_catch", dt / steps * 1e6,
            f"{fps:.0f}fps {fps / n:.0f}fps/dev B={bsz}")
        fps_r, dt_r, _ = arm(n, replay=True)
        row(f"scaling_replay_n{n}_catch", dt_r / steps * 1e6,
            f"{fps_r:.0f}fps {fps_r / max(fps, 1e-9) * 100:.0f}%_of_plain "
            f"ratio=0.25")


def bench_fps_host_loop(duration=6.0):
    """MonoBeast/PolyBeast host actor loop throughput (§4 FPS analogue)."""
    from repro.configs.atari_impala import small_train
    from repro.core.actor_pool import ActorPool, start_inference_thread
    from repro.core.batcher import BatchingQueue, DynamicBatcher
    from repro.envs import catch
    from repro.envs.base import HostEnv
    from repro.models.convnet import init_agent, minatar_net
    env0 = catch.make()
    tc = small_train(unroll_length=20, batch_size=8, num_actors=8)
    init_fn, apply_fn = minatar_net(env0.obs_shape, env0.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
    policy = jax.jit(lambda obs: apply_fn(params, obs).policy_logits)
    inference = DynamicBatcher(max_batch_size=8, timeout_ms=2)
    learner_queue = BatchingQueue(tc.batch_size, batch_dim=1, max_items=64)
    pool = ActorPool(lambda seed: HostEnv(env0, seed), tc.num_actors,
                     tc.unroll_length, inference, learner_queue)
    start_inference_thread(inference,
                           lambda obs: policy(jnp.asarray(obs)))
    pool.start()
    consumed = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration:
        batch = learner_queue.get(timeout=1.0)
        if batch is not None:
            consumed += batch["reward"].size
    dt = time.perf_counter() - t0
    pool.stop()
    row("fps_host_loop_catch", dt * 1e6, f"{consumed/dt:.0f}fps")


def bench_dynamic_batcher():
    from repro.core.batcher import DynamicBatcher
    b = DynamicBatcher(max_batch_size=16, timeout_ms=1)
    n_req = 512
    done = threading.Event()

    def consumer():
        served = 0
        while served < n_req:
            got = b.get_batch(timeout=2.0)
            if got is None:
                break
            inputs, respond, n = got
            respond(inputs)
            served += n
        done.set()

    t = threading.Thread(target=consumer, daemon=True)
    x = np.zeros((84,), np.float32)
    t0 = time.perf_counter()
    t.start()
    threads = [threading.Thread(target=lambda: [b.compute(x)
                                                for _ in range(n_req // 16)])
               for _ in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    done.wait(timeout=5)
    dt = time.perf_counter() - t0
    row("dynamic_batcher_roundtrip", dt / n_req * 1e6,
        f"{n_req/dt:.0f}req/s")


def bench_attention():
    import dataclasses
    from repro.configs import get_reduced_config
    from repro.models import attention as A
    from repro.models.common import split_params
    cfg = dataclasses.replace(get_reduced_config("qwen3-32b"),
                              attn_chunk=128)
    params = split_params(A.attn_init(jax.random.PRNGKey(0), cfg, "attn"))[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 512, cfg.d_model),
                          jnp.float32)
    pos = jnp.arange(512)
    for impl in ("xla", "xla_chunked", "xla_chunked_skip"):
        f = jax.jit(lambda x, impl=impl: A.attn_apply(
            params, x, cfg=cfg, kind="attn", positions=pos, impl=impl)[0])
        us = timeit(lambda: jax.block_until_ready(f(x)), n=10)
        row(f"attention_{impl}_S512", us, "")


def bench_generate():
    from repro.configs import get_reduced_config
    from repro.core import generate as G
    from repro.models import model as M
    cfg = get_reduced_config("qwen3-4b")
    params, _ = M.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (8, 15), 0,
                                cfg.vocab_size)

    def f():
        return jax.block_until_ready(
            G.generate(params, prompt, jax.random.PRNGKey(2), cfg=cfg,
                       num_steps=32)["tokens"])

    us = timeit(f, n=5)
    row("generate_B8_P15_N32", us, f"{8*32/(us/1e6):.0f}tok/s")


def bench_ssd_chunk():
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    bh, l, n, p = 8, 128, 64, 64
    c = jnp.asarray(rng.normal(0, 1, (bh, l, n)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (bh, l, n)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (bh, l, p)), jnp.float32)
    da = jnp.asarray(-rng.random((bh, l, 1)) * 0.1, jnp.float32)
    h = jnp.asarray(rng.normal(0, 1, (bh, p, n)), jnp.float32)
    f = jax.jit(lambda *a: ref.ref_ssd_chunk(*a))
    us = timeit(lambda: jax.block_until_ready(f(c, b, x, da, h)[0]), n=10)
    row("ssd_chunk_jnp_BH8_L128", us, "")
    g = jax.jit(lambda *a: ops.ssd_chunk(*a))
    us = timeit(lambda: jax.block_until_ready(g(c, b, x, da, h)[0]), n=3)
    row("ssd_chunk_pallas_interp", us, "interpret-mode")


def bench_serving():
    """Continuous vs static batching on the DecodeSession server under
    Poisson arrivals with heavy-tail (lognormal) prompt/generation lengths
    — the workload where per-step admission pays: static batching holds
    freed slots hostage to the longest generation in the batch. Per-request
    keys are pinned so both policies serve IDENTICAL token streams; rows
    report request-latency p50/p99 (us) and sustained generated tok/s."""
    from repro.configs import get_reduced_config
    from repro.launch.serve import Server
    from repro.models import model as M

    cfg = get_reduced_config("qwen3-4b")
    params, _ = M.init(jax.random.PRNGKey(0), cfg)
    n_req = 12 if SMALL else 48
    max_batch = 4
    max_len = 24 if SMALL else 64
    rng = np.random.default_rng(0)
    plens = np.clip(rng.lognormal(1.0, 0.8, n_req).astype(int) + 1,
                    1, max_len // 2)
    glens = np.clip(rng.lognormal(1.2, 1.0, n_req).astype(int) + 1,
                    1, max_len // 2)
    gaps = rng.exponential(0.005, n_req)         # Poisson arrivals
    prompts = [rng.integers(0, cfg.vocab_size, size=int(p)) for p in plens]
    keys = [np.asarray(jax.random.PRNGKey(1000 + i)) for i in range(n_req)]

    def run(policy):
        server = Server(cfg, params, max_batch=max_batch, max_len=max_len,
                        policy=policy).start()
        t0 = time.perf_counter()
        handles = []
        for i in range(n_req):
            time.sleep(gaps[i])
            handles.append(server.submit(prompts[i],
                                         max_tokens=int(glens[i]),
                                         key=keys[i]))
        tokens = sum(h.result(timeout=600).shape[0] - h.prompt.shape[0]
                     for h in handles)
        dt = time.perf_counter() - t0
        lat = np.asarray([h.t_done - h.t_submit for h in handles])
        server.stop()
        return lat, tokens / dt, server.steps

    run("continuous")   # warmup: pay the per-bucket prefill compiles once
    stats = {}
    for policy in ("continuous", "static"):
        lat, tps, steps = run(policy)
        stats[policy] = tps
        for q, v in (("p50", np.quantile(lat, 0.5)),
                     ("p99", np.quantile(lat, 0.99))):
            row(f"serving_{policy}_{q}", v * 1e6,
                f"{tps:.1f}tok/s steps={steps}")
    row("serving_speedup", 0.0,
        f"continuous/static={stats['continuous']/stats['static']:.2f}x")


def bench_kernels():
    """xla reference vs Pallas kernel per hot-path op (flash attention,
    decode attention, SSD chunk, V-trace) at a small and a paper-ish shape,
    with achieved-vs-roofline accounting from
    ``launch.roofline.kernel_roofline`` at the measured dims. On CPU the
    kernels execute in interpret mode (see kernels/compat.py), so
    ``of_roofline`` documents interpreter overhead only; on a TPU the same
    rows measure real kernel efficiency against the analytic roofline."""
    from repro.core.vtrace import vtrace_from_importance_weights
    from repro.kernels import ops, ref
    from repro.launch.roofline import kernel_roofline

    rng = np.random.default_rng(0)

    def norm(*shape):
        return jnp.asarray(rng.normal(0, 1, shape), jnp.float32)

    def versus(name, ref_call, kern_call, kern, dims, n_ref=10, n_kern=2):
        us_ref = timeit(lambda: jax.block_until_ready(ref_call()), n=n_ref)
        row(f"{name}_xla", us_ref, "")
        us_k = timeit(lambda: jax.block_until_ready(kern_call()), n=n_kern,
                      warmup=1)
        r = kernel_roofline(kern, dtype_bytes=4, **dims)
        row(f"{name}_kernel", us_k,
            f"vs_xla={us_ref / us_k:.3f}x "
            f"roofline_us={r['roofline_s'] * 1e6:.2f} "
            f"of_roofline={100 * r['roofline_s'] * 1e6 / us_k:.3f}% "
            f"bound={r['bound']}")

    s_big = 256 if SMALL else 2048
    for tag, b, h, kh, s, hd in (("small", 2, 4, 2, 128, 32),
                                 ("paperish", 1, 8, 4, s_big, 64)):
        q, k, v = norm(b, h, s, hd), norm(b, kh, s, hd), norm(b, kh, s, hd)
        blk = min(128, s)
        fx = jax.jit(lambda q, k, v: ref.ref_flash_attention(q, k, v))
        fk = jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, block_q=blk, block_k=blk))
        versus(f"flash_{tag}_S{s}", lambda: fx(q, k, v),
               lambda: fk(q, k, v), "flash_attention",
               dict(b=b, h=h, kh=kh, s=s, hd=hd, window=0))

    cap_big = 512 if SMALL else 4096
    for tag, b, h, kh, cap, hd in (("small", 8, 4, 2, 128, 32),
                                   ("paperish", 32, 8, 4, cap_big, 64)):
        q, k, v = norm(b, h, hd), norm(b, kh, cap, hd), norm(b, kh, cap, hd)
        slot = jnp.arange(cap, dtype=jnp.int32)
        pos = jnp.int32(cap - 1)
        dx = jax.jit(lambda q, k, v: ref.ref_decode_attention(
            q, k, v, slot, pos))
        dk = jax.jit(lambda q, k, v: ops.decode_attention(
            q, k, v, slot, pos, block_k=min(128, cap)))
        versus(f"decode_{tag}_T{cap}", lambda: dx(q, k, v),
               lambda: dk(q, k, v), "decode_attention",
               dict(b=b, h=h, kh=kh, s=cap, hd=hd), n_kern=3)

    for tag, bh, l, n, p in (("small", 4, 64, 32, 32),
                             ("paperish", 8 if SMALL else 64,
                              128 if SMALL else 256, 64, 64)):
        c, bm, x = norm(bh, l, n), norm(bh, l, n), norm(bh, l, p)
        da = jnp.asarray(-rng.random((bh, l, 1)) * 0.1, jnp.float32)
        hp = norm(bh, p, n)
        sx = jax.jit(ref.ref_ssd_chunk)
        sk = jax.jit(lambda *a: ops.ssd_chunk(*a))
        versus(f"ssd_{tag}_L{l}", lambda: sx(c, bm, x, da, hp)[0],
               lambda: sk(c, bm, x, da, hp)[0], "ssd_chunk",
               dict(bh=bh, l=l, n=n, p=p))

    t, b = 80, 256
    args = [norm(t, b) for _ in range(4)] + [norm(b)]
    vx = jax.jit(vtrace_from_importance_weights)
    vk = jax.jit(ops.vtrace_from_importance_weights_kernel)
    versus(f"vtrace_T{t}_B{b}", lambda: vx(*args), lambda: vk(*args),
           "vtrace", dict(t=t, b=b), n_kern=3)


def roofline_table():
    """Print the §Roofline table from the dry-run artifacts (preferring the
    post-§Perf optimized sweep)."""
    files = (sorted(glob.glob("experiments/dryrun_optimized/*.json"))
             or sorted(glob.glob("experiments/dryrun/*.json"))
             or sorted(glob.glob("experiments/dryrun_baseline/*.json")))
    if not files:
        print("# roofline: no dry-run artifacts; run "
              "`python -m repro.launch.dryrun --all` first")
        return
    print("# arch,shape,mesh,rules,compute_s,memory_s,collective_s,"
          "bottleneck,useful_ratio,mem_GiB")
    for f in files:
        d = json.load(open(f))
        r = d["roofline"]
        print(f"roofline,{d['arch']},{d['shape']},{d['mesh']},{d['rules']},"
              f"{r['compute_s']:.2e},{r['memory_s']:.2e},"
              f"{r['collective_s']:.2e},{r['bottleneck']},"
              f"{r['useful_ratio']:.2f},"
              f"{d['memory']['per_device_total']/2**30:.2f}")


_SUITES = {
    "vtrace": bench_vtrace,
    "learner": bench_learner_step,
    "fps": bench_fps_on_device,
    "pipeline": bench_pipeline,
    "replay": bench_replay,
    "scaling": bench_scaling,
    "host_loop": bench_fps_host_loop,
    "batcher": bench_dynamic_batcher,
    "attention": bench_attention,
    "generate": bench_generate,
    "serving": bench_serving,
    "ssd": bench_ssd_chunk,
    "kernels": bench_kernels,
    "roofline": roofline_table,
}


def main(argv=None) -> None:
    import argparse
    import os
    p = argparse.ArgumentParser()
    p.add_argument("--suite", choices=["all"] + sorted(_SUITES),
                   action="append", default=None,
                   help="suite to run; repeatable (default: everything)")
    p.add_argument("--small", action="store_true",
                   help="CI-smoke scale (short training arms)")
    p.add_argument("--out-dir", default=".",
                   help="where BENCH_<suite>.json artifacts are written")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    global SMALL
    SMALL = args.small
    os.makedirs(args.out_dir, exist_ok=True)
    suites = args.suite or ["all"]
    if "all" in suites:
        suites = list(_SUITES)
    if (suites == ["scaling"]
            and "--xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # must land before jax initialises its backend (no device query has
        # happened yet — suites run after this point). Only when scaling is
        # the SOLE suite: forcing 8 CPU devices would skew every other
        # suite's timings in the same process (run scaling standalone to
        # get the full 1/2/4/8 curve).
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
    print("name,us_per_call,derived")
    for name in suites:
        _RESULTS.clear()
        _SUITES[name]()
        path = os.path.join(args.out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump({"suite": name, "small": SMALL,
                       "backend": jax.default_backend(),
                       "devices": jax.device_count(),
                       "rows": list(_RESULTS)}, f, indent=1)
        print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
