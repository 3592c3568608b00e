"""Smoke run of the main paths on a TPU, through the normal entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the data-parallel learner

One chip: (A) IMPALA training — ``repro.launch.train.main`` with the deep
ResNet agent, the pipelined, donating ``DeviceSource`` and the V-trace
kernel; (B) zamba2-2.7b at its published widths behind
``repro.launch.serve.Server`` with the flash-attention, SSD and
decode-attention kernels; then each of the four Pallas kernels against its
float32 reference at the shapes A and B ran. Weights and inputs are
random, from ``SEED``.

Four chips: (A) with ``--mesh-data 4`` (``ShardedDeviceSource`` and the
sharded learner step), then learner-step loss parity on fixed batches,
mesh 1 against mesh 4.

Everything runs in this one process (a chip belongs to one process), and no
failure is caught: a failed phase or check exits nonzero. Without a TPU it
exits nonzero before any phase. The last line of stdout is a JSON object
naming the device. The other lines (wall and compile times, losses, peak
device memory) are for information only; none is a benchmark metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# Phase A: the train CLI as a user calls it. catch's unroll is T=20.
TRAIN_ARGV = ["--mode", "rl-agent", "--agent", "deep", "--vtrace-impl",
              "kernel", "--batch", "32", "--steps", "5"]
UNROLL = 20
SEED = 0

# Phase B: serving at published widths. A recurrent mixer prefills at the
# exact prompt length (one compile per length), and prompts stay within
# one 256-token SSD chunk; 37 is not a multiple of any tile.
SERVE = dict(arch="zamba2-2.7b", max_batch=8, max_len=512,
             prompt_lens=(37, 256), requests=8, gen_tokens=32)

# Max abs error allowed between each kernel and its reference
# (kernels/ref.py in float32 at "highest" matmul precision), on the seeded
# inputs of kernel_parity().
TOLERANCES = {
    # f32 elementwise recursion in the scan's order; only the rounding of
    # fused multiply-adds can differ, on |acc| < 30.
    "vtrace": 1e-4,
    # bf16 q/k/v and a bf16 output, as the model serves, against a float32
    # reference on the same values: half a bf16 ulp at |o| < 4 is 7.8e-3,
    # plus the probabilities rounded to bf16 (2^-9) for p @ v on the MXU.
    "flash_attention": 2e-2,
    "decode_attention": 2e-2,
    # f32 operands; the kernel's matmuls other than the cumsum run at the
    # MXU's default precision: with operands rounded to bf16, the
    # reference moves by 7.6e-3 on these inputs (|y| < 2).
    "ssd_chunk": 2e-2,
}

# Mesh 1 against mesh 4, both at "highest" precision: the layouts differ
# only in the order of the batch reductions and the gradient all-reduce,
# about 1e-6 relative per step over three steps.
PARITY_RTOL = 1e-4


class CompileClock:
    """Sums XLA backend compile time and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self):
        out = (f"compile {self.seconds:.1f}s in {self.compiles} programs, "
               f"{self.cache_hits} persistent-cache hits")
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        return out


def peak_bytes():
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def check(ok, what) -> None:
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def all_finite(tree) -> bool:
    return all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree))


def device_gate(need: int):
    devices = jax.devices()
    d = devices[0]
    print(f"devices: {devices}")
    print(f"platform={d.platform} kind={d.device_kind} count={len(devices)}")
    if d.platform != "tpu":
        sys.exit(f"no TPU: JAX runs on {d.platform}; refusing to fall back")
    if len(devices) < need:
        sys.exit(f"{need} chips needed, {len(devices)} visible")
    return d


def phase_a(argv, clock):
    """IMPALA training through the train CLI; returns the Runtime."""
    from repro.launch import train
    t0 = time.perf_counter()
    runtime = train.main(argv)
    params = jax.block_until_ready(runtime.params)
    wall = time.perf_counter() - t0
    steps = int(argv[argv.index("--steps") + 1])
    batch = int(argv[argv.index("--batch") + 1])
    loss = float(runtime.metrics["loss"])
    check(runtime.frames == steps * UNROLL * batch, runtime.frames)
    check(np.isfinite(loss) and all_finite(params), "non-finite training")
    print(f"phase A: {steps} steps, {runtime.frames} frames, last loss "
          f"{loss:+.6f}, wall {wall:.1f}s ({clock.lap()}), "
          f"peak bytes {peak_bytes()}")
    return runtime


def phase_b(clock, *, cfg=None, max_batch=SERVE["max_batch"],
            max_len=SERVE["max_len"], prompt_lens=SERVE["prompt_lens"],
            requests=SERVE["requests"], gen_tokens=SERVE["gen_tokens"]):
    """Serve ``requests`` prompts through ``Server`` with every LM kernel
    on the path; checks each answer's prompt echo and token budget."""
    from repro.configs import get_config
    from repro.configs.base import ImplContext
    from repro.launch.serve import Server
    from repro.models import model as model_lib

    cfg = ImplContext(attn="kernel", ssd="kernel").apply(
        cfg or get_config(SERVE["arch"]))
    t0 = time.perf_counter()
    params = jax.jit(lambda k: model_lib.init(k, cfg)[0])(
        jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    jax.block_until_ready(params)
    print(f"phase B: {cfg.name} d_model={cfg.d_model} layers="
          f"{cfg.num_layers} params={n_params} init "
          f"{time.perf_counter() - t0:.1f}s")
    server = Server(cfg, params, max_batch=max_batch, max_len=max_len,
                    default_max_tokens=gen_tokens, seed=SEED).start()
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size,
                            prompt_lens[i % len(prompt_lens)])
               for i in range(requests)]
    t1 = time.perf_counter()
    try:
        handles = [server.submit(p, max_tokens=gen_tokens) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
    finally:
        server.stop()
    wall = time.perf_counter() - t1
    for p, r in zip(prompts, results):
        check(r.shape == (p.shape[0] + gen_tokens,), (r.shape, p.shape))
        check(np.array_equal(r[:p.shape[0]], p), "prompt not echoed")
        check(((r >= 0) & (r < cfg.vocab_size)).all(), "token out of range")
    check(server.served == requests, server.served)
    check(server.tokens_out == requests * gen_tokens, server.tokens_out)
    print(f"phase B: served {server.served}/{requests} requests, "
          f"{server.tokens_out} tokens, {server.steps} decode steps, prompt "
          f"lengths {sorted(set(prompt_lens))}, wall {wall:.1f}s "
          f"({clock.lap()}), peak bytes {peak_bytes()}")
    return cfg


def _max_err(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def kernel_parity(cfg, clock, *, b=32, prompt_lens=SERVE["prompt_lens"],
                  max_batch=SERVE["max_batch"], max_len=SERVE["max_len"]):
    """Each kernels/ops.py kernel against its kernels/ref.py reference at
    the shapes phases A (V-trace) and B (the LM kernels) ran."""
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    t = UNROLL
    dt = jnp.dtype(cfg.dtype)
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    nh = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    n, p = cfg.ssm_state, cfg.ssm_head_dim
    normal = lambda *s: rng.standard_normal(s, np.float32)  # noqa: E731
    cases = []   # (kernel, shape label, kernel fn, reference fn)

    deltas = jnp.asarray(normal(t, b))
    dcs = jnp.asarray(0.99 * rng.random((t, b), np.float32))
    cases.append(("vtrace", f"T{t} B{b}", lambda: ops.vtrace_acc(deltas, dcs),
                  lambda: ref.ref_vtrace_scan(deltas, dcs)))

    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
    for s in prompt_lens:
        q = jnp.asarray(normal(1, h, s, hd), dt)
        k = jnp.asarray(normal(1, kh, s, hd), dt)
        v = jnp.asarray(0.5 * normal(1, kh, s, hd), dt)
        blk = min(s, 128)
        cases.append((
            "flash_attention", f"B1 H{h} K{kh} S{s} hd{hd}",
            lambda q=q, k=k, v=v, blk=blk: ops.flash_attention(
                q, k, v, block_q=blk, block_k=blk),
            lambda q=q, k=k, v=v: ref.ref_flash_attention(*f32(q, k, v))))

    q = jnp.asarray(normal(max_batch, h, hd), dt)
    k = jnp.asarray(normal(max_batch, kh, max_len, hd), dt)
    v = jnp.asarray(0.5 * normal(max_batch, kh, max_len, hd), dt)
    pos = jnp.asarray(rng.integers(1, max_len, max_batch), jnp.int32)
    slot = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32),
                            (max_batch, max_len))
    cases.append((
        "decode_attention", f"B{max_batch} H{h} K{kh} cap{max_len} hd{hd}",
        lambda: ops.decode_attention(q, k, v, slot, pos,
                                     block_k=min(max_len, 128)),
        lambda: ref.ref_decode_attention(*f32(q, k, v), slot, pos)))

    for s in prompt_lens:
        ln = min(s, cfg.ssm_chunk)
        c = jnp.asarray(0.5 * normal(nh, ln, n))
        bm = jnp.asarray(0.5 * normal(nh, ln, n))
        xdt = jnp.asarray(0.05 * normal(nh, ln, p))
        # da = dt * a: dt in [1e-3, 1e-1] (the init's softplus range), a=-e
        da = jnp.asarray(-np.e * rng.uniform(1e-3, 1e-1, (nh, ln, 1))
                         .astype(np.float32))
        h0 = jnp.asarray(0.1 * normal(nh, p, n))
        cases.append((
            "ssd_chunk", f"BH{nh} L{ln} N{n} P{p}",
            lambda c=c, bm=bm, xdt=xdt, da=da, h0=h0: ops.ssd_chunk(
                c, bm, xdt, da, h0),
            lambda c=c, bm=bm, xdt=xdt, da=da, h0=h0: ref.ref_ssd_chunk(
                c, bm, xdt, da, h0)))

    worst = {}
    for name, shape, run, reference in cases:
        got = jax.tree.leaves(run())
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(reference())
        err = max(_max_err(g, w) for g, w in zip(got, want))
        tol = TOLERANCES[name]
        print(f"parity {name:16s} {shape:28s} max abs err {err:.3e} "
              f"(tolerance {tol:.0e})")
        check(err <= tol, f"{name} {shape}: {err} > {tol}")
        worst[name] = max(err, worst.get(name, 0.0))
    check(set(worst) == set(TOLERANCES), worst)
    print(f"parity: all {len(cases)} cases within tolerance ({clock.lap()})")


def check_sharded_source(runtime, n):
    """The mesh-n learner batch spans n devices, one shard each, and each
    actor stream's carry lives on a device of its own."""
    src = runtime.source
    batch = src.next_batch(runtime.params)
    try:
        for key, x in batch.items():
            shard_devs = [s.device for s in x.addressable_shards]
            check(len(x.sharding.device_set) == n, (key, x.sharding))
            check(len(set(shard_devs)) == n, (key, shard_devs))
        carry_devs = []
        for carry in src._carries:          # one env carry per actor stream
            devs = {d for leaf in jax.tree.leaves(carry)
                    for d in leaf.devices()}
            check(len(devs) == 1, devs)
            carry_devs.append(devs.pop())
        check(len(set(carry_devs)) == n, carry_devs)
    finally:
        src.stop()
    print(f"phase A: learner batch over {n} devices "
          f"{sorted(d.id for d in batch['obs'].sharding.device_set)}; "
          f"actor carries on {[d.id for d in carry_devs]}")


def mesh_loss_parity(clock, *, n=4, b=32, steps=3):
    """Learner-step losses on fixed batches, mesh 1 against mesh n."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs.atari_impala import small_train
    from repro.core import learner
    from repro.envs import catch
    from repro.launch.mesh import make_data_mesh
    from repro.models.convnet import impala_deep, init_agent
    from repro.optim import make_optimizer

    env = catch.make()
    tc = small_train(total_steps=steps, learning_rate=2e-3, batch_size=b)
    init_fn, apply_fn = impala_deep(env.obs_shape, env.num_actions)
    params0, _ = init_agent(init_fn, jax.random.PRNGKey(SEED))
    opt = make_optimizer(tc)
    rng = np.random.default_rng(SEED)
    t = UNROLL
    batches = [{
        "obs": rng.random((t + 1, b) + env.obs_shape).astype(np.float32),
        "action": rng.integers(0, env.num_actions, (t, b)).astype(np.int32),
        "behavior_logits": rng.normal(
            0, 1, (t, b, env.num_actions)).astype(np.float32),
        "reward": rng.normal(0, 1, (t, b)).astype(np.float32),
        "done": rng.random((t, b)) > 0.9,
    } for _ in range(steps)]

    def losses_on(m):
        mesh = make_data_mesh(m)
        step = jax.jit(learner.make_train_step(apply_fn, opt, tc, mesh=mesh,
                                               vtrace_impl="kernel"))
        params = jax.device_put(params0, NamedSharding(mesh, PartitionSpec()))
        opt_state = opt.init(params)
        out = []
        for i, batch in enumerate(batches):
            batch = {k: jax.device_put(v, NamedSharding(mesh, PartitionSpec(
                *([None, "data"] + [None] * (v.ndim - 2)))))
                for k, v in batch.items()}
            params, opt_state, metrics = step(params, opt_state,
                                              jnp.int32(i), batch)
            out.append(float(metrics["loss"]))
        return np.asarray(out)

    with jax.default_matmul_precision("highest"):
        l1, ln = losses_on(1), losses_on(n)
    diff = np.abs(l1 - ln)
    print(f"parity mesh1 losses {l1.tolist()}")
    print(f"parity mesh{n} losses {ln.tolist()}")
    print(f"parity mesh1 vs mesh{n}: max abs diff {diff.max():.3e}, max rel "
          f"{(diff / np.abs(l1)).max():.3e} (rtol {PARITY_RTOL:.0e}) "
          f"({clock.lap()})")
    np.testing.assert_allclose(ln, l1, rtol=PARITY_RTOL, atol=0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-4 learner and its mesh-1 parity")
    args = ap.parse_args(argv)
    use_compile_cache()
    need = 4 if args.four_chips else 1
    device = device_gate(need)
    clock = CompileClock()
    t0 = time.perf_counter()
    from repro.kernels.compat import resolve_interpret

    if args.four_chips:
        runtime = phase_a(TRAIN_ARGV + ["--mesh-data", str(need)], clock)
        check_sharded_source(runtime, need)
        del runtime
        mesh_loss_parity(clock, n=need)
    else:
        phase_a(TRAIN_ARGV, clock)
        cfg = phase_b(clock)
        kernel_parity(cfg, clock)

    stats = resolve_interpret.stats()
    print(f"kernel modes: {stats}; total wall "
          f"{time.perf_counter() - t0:.1f}s")
    check(stats["fallbacks"] == 0, f"kernels fell back to interpret: {stats}")
    check(stats["compiled"] > 0, f"no kernel was compiled: {stats}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
