"""IMPALA actor/learner training cell.

The program's own path: ``DeviceSource.for_env``, the jitted
``learner.make_train_step`` with the configured V-trace impl, and one
``core.runtime.Runtime`` driving them. The environment is the
Atari-shaped synthetic env of ``envs/atari_synth.py``.

Set-up builds that one Runtime, runs its first ``check_steps`` learner
steps from the seed (which also compiles every program the window runs)
and hands the same Runtime, source, step and state to the window. The
check replays those steps in the plain float32 reference
(reference/impala.py) from the same initial weights and batches and
compares the worst step's loss and the first step's loss, the first
gradient as RMSProp holds it, and the parameters' change.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chip import harness, weights
from chip.envs import atari_synth
from chip.harness import log
from chip.reference import impala as ref


class WindowClosed(Exception):
    pass


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _leaf_norms(tree):
    return np.array([float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
                     for x in jax.tree.leaves(tree)])


def _block(n, most=128):
    return max(b for b in range(1, most + 1) if n % b == 0)


def leaf_gaps(prog, want, keep=None):
    """Per leaf, |prog - want| / max(want, median(want)) (NaN where
    ``keep`` leaves it out)."""
    prog, want = np.asarray(prog), np.asarray(want)
    keep = np.ones(want.shape, bool) if keep is None else keep
    floor = np.median(want[keep])
    return np.where(keep, np.abs(prog - want) / np.maximum(want, floor),
                    np.nan)


def worst_leaf_gap(prog, want, keep=None):
    return float(np.nanmax(leaf_gaps(prog, want, keep)))


def hp_items(hp):
    """The reference's hyperparameters (a hashable static argument)."""
    return tuple(sorted(dict(
        discount=hp.discount, vtrace_rho_clip=hp.vtrace_rho_clip,
        vtrace_c_clip=hp.vtrace_c_clip, baseline_cost=hp.baseline_cost,
        entropy_cost=hp.entropy_cost, grad_clip=hp.grad_clip,
        total_steps=hp.total_steps, learning_rate=hp.learning_rate,
        rmsprop_decay=hp.rmsprop_decay, rmsprop_eps=hp.rmsprop_eps).items()))


def readings(first, refrun, per_leaf=False):
    """The compared numbers of one run (``first``: the program's or a
    stand-in's first steps) against the reference's (``refrun``);
    ``per_leaf``: the losses' gaps and each leaf's instead."""
    decay = refrun["decay"]
    losses = [abs(a - b) / s for a, b, s in
              zip(first["losses"], refrun["losses"], refrun["scales"])]
    # RMSProp's first state is (1 - decay) * g * g, elementwise
    g_prog = np.sqrt(np.array([float(np.sum(np.asarray(m, np.float64)))
                               for m in jax.tree.leaves(first["ms1"])])
                     / (1 - decay))
    g_ref = refrun["grad_norms"]
    keep = g_ref >= 1e-3 * np.median(g_ref)
    d_prog = _leaf_norms(jax.tree.map(np.subtract, first["params_n"],
                                      first["params0"]))
    d_ref = refrun["delta_norms"]
    if per_leaf:
        return {"loss_gap": np.array(losses),
                "grad_gap": leaf_gaps(g_prog, g_ref),
                "update_gap": leaf_gaps(d_prog, d_ref, keep)}
    return {"loss_gap": max(losses), "loss_gap_step1": losses[0],
            "grad_gap": worst_leaf_gap(g_prog, g_ref),
            "update_gap": worst_leaf_gap(d_prog, d_ref, keep)}


class _Recorder:
    """The source as the Runtime drives it: keeps a host copy of each batch
    while ``keep`` is on (the set-up steps that the check replays), and
    the shapes, types and placements of a batch."""

    def __init__(self, source):
        self.source, self.kept, self.keep = source, [], True
        self.frames_per_batch = source.frames_per_batch
        self.spec = None

    def start(self, params):
        self.source.start(params)

    def stop(self):
        self.source.stop()

    def next_batch(self, params):
        batch = self.source.next_batch(params)
        if self.spec is None:
            self.spec = harness.abstract(batch)
        if self.keep:
            self.kept.append(_host(batch))
        return batch


class Run:
    def __init__(self, cell, args, devices):
        if len(devices) != 1:
            raise ValueError("the IMPALA runner drives one chip")
        self.cell, self.args, self.devices = cell, args, devices
        self.cfg, self.mix = cell.config, cell.traffic
        self.e2e, self.counters = {}, {}
        self.attempted = self.failed = 0
        from repro.configs.atari_impala import TRAIN
        self.hp = dataclasses.replace(
            TRAIN, unroll_length=self.mix["unroll_length"],
            batch_size=self.mix["batch_per_chip"], **self.cfg["train"])

    # -- building the program ----------------------------------------------

    def _first_steps(self, seed):
        """Build the one Runtime, run its first ``check_steps`` steps from
        the seed and record what the check needs; returns (runtime,
        record)."""
        from repro.core import learner
        from repro.core.runtime import Runtime
        from repro.core.sources import DeviceSource
        from repro.models.convnet import impala_deep
        from repro.optim import make_optimizer

        t0 = time.perf_counter()
        net, hp = self.cfg["model"], self.hp
        shape = dict(obs_shape=tuple(net["obs_shape"]),
                     num_actions=net["num_actions"],
                     channels=tuple(net["channels"]), fc=net["fc"])
        _, apply_fn = impala_deep(shape["obs_shape"], shape["num_actions"],
                                  channels=shape["channels"], fc=shape["fc"])
        params = weights.impala_params(weights.seed_key(seed, "weights"),
                                       **shape)
        opt = make_optimizer(hp)
        source = _Recorder(DeviceSource.for_env(
            atari_synth.make(**self.cfg["env"]), apply_fn,
            unroll_length=hp.unroll_length, batch_size=hp.batch_size,
            key=weights.seed_key(seed, "actors"),
            pipelined=self.mix["pipelined"]))
        step_fn = jax.jit(learner.make_train_step(
            apply_fn, opt, hp, vtrace_impl=self.cfg["impl"]["vtrace"]))
        first = {"params0": _host(params), "losses": []}

        def on_metrics(step, metrics):
            first["losses"].append(float(metrics["loss"]))
            if step == 0:
                first["ms1"] = _host(rt.opt_state["ms"])

        rt = Runtime(source, step_fn, params, opt.init(params),
                     total_steps=self.mix["check_steps"], log_every=0,
                     on_metrics=on_metrics)
        rt.run()
        source.keep = False
        first["batches"], source.kept = source.kept, []
        first["params_n"] = _host(rt.params)
        # the learner step's and the unroll's arguments as the window passes
        # them, for hlo_texts
        src = source.source
        self._programs = {
            "jit_train_step": (step_fn, harness.abstract(
                (rt.params, rt.opt_state, jnp.int32(0))) + (source.spec,)),
            "jit_unroll": (src._unroll, harness.abstract(
                (rt.params, src._carry,
                 jax.random.split(src._key)[1])))}
        log(f"first {len(first['losses'])} steps from seed {seed}: "
            f"{time.perf_counter() - t0:.1f}s")
        return rt, first

    def setup(self):
        self.rt, self.first = self._first_steps(self.args.seed)

    # -- the measured window ------------------------------------------------

    def window(self, seconds, tracer):
        """The set-up's Runtime, run on from its last step: steps are sent
        until ``seconds`` have passed, and the window closes once all that
        was sent has run. Each loss is read ``read_lag_steps`` steps after
        its step was sent, so the device keeps working while the host
        stalls."""
        rt = self.rt
        losses, pending = [], collections.deque()
        lag = int(self.mix["read_lag_steps"])
        deadline = last = None
        longest = 0.0

        def on_metrics(step, metrics):
            nonlocal last, longest
            pending.append(metrics["loss"])
            if len(pending) > lag:
                losses.append(float(pending.popleft()))
            now = time.perf_counter()
            longest = max(longest, now - last)
            last = now
            if now >= deadline:
                raise WindowClosed

        rt.start_step, rt.total_steps = rt.total_steps, 2**62
        rt.on_metrics = on_metrics
        frames0 = rt.frames
        if tracer:
            tracer.start()
        t0 = last = time.perf_counter()
        deadline = t0 + seconds
        try:
            rt.run()
        except WindowClosed:
            pass
        jax.block_until_ready(rt.params)
        elapsed = time.perf_counter() - t0
        drain = time.perf_counter() - deadline
        if tracer:
            tracer.stop()
        losses += [float(x) for x in pending]
        frames = rt.frames - frames0
        steps = frames // rt.source.frames_per_batch
        self.attempted = steps
        self.failed = sum(not math.isfinite(x) for x in losses)
        self.e2e["frames_per_s"] = frames / elapsed
        net = self.cfg["model"]
        self.counters.update(
            steps=steps, frames=frames, window_s=elapsed,
            unroll_length=self.hp.unroll_length, batch=self.hp.batch_size,
            obs_shape=net["obs_shape"], channels=net["channels"],
            fc=net["fc"], num_actions=net["num_actions"])
        log(f"window: {steps} learner steps, {frames} frames in "
            f"{elapsed:.3f}s ({drain:.3f}s past "
            f"the deadline); longest host gap between steps "
            f"{longest:.3f}s; last loss {losses[-1] if losses else None}")
        self.rt = None

    def hlo_texts(self):
        """{program: compiled HLO text} of the learner step and the actors'
        unroll, which names the scope of each instruction (chip.spans);
        lowered from the arguments' shapes, and compiled from the
        persistent cache, after the window."""
        return {name: fn.lower(*args).compile().as_text()
                for name, (fn, args) in self._programs.items()}

    # -- correctness --------------------------------------------------------

    def _reference(self, first, low=False, columns=None):
        """The reference's first steps from the same weights and batches
        (``low``: the fp8 control); ``columns`` keeps only those batch
        columns (a planted fault)."""
        hp = self.hp
        items = hp_items(hp)
        params = jax.device_put(first["params0"], self.devices[0])
        ms = jax.tree.map(jnp.zeros_like, params)
        out = {"losses": [], "scales": [], "decay": hp.rmsprop_decay,
               "params0": first["params0"]}
        for i, batch in enumerate(first["batches"]):
            batch = {k: v[:, columns] if columns is not None else v
                     for k, v in batch.items()}
            n = batch["obs"].shape[0] * batch["obs"].shape[1]
            batch = jax.device_put(batch, self.devices[0])
            params, ms, total, parts, grads = ref.train_step(
                params, ms, jnp.int32(i), batch, hp_items=items,
                block=_block(n), low=low)
            pg, bl, ent = (float(x) for x in parts)
            out["losses"].append(float(total))
            out["scales"].append(abs(pg) + hp.baseline_cost * abs(bl)
                                 + hp.entropy_cost * abs(ent))
            if i == 0:
                out["ms1"] = _host(ms)
                out["grad_norms"] = _leaf_norms(grads)
        out["params_n"] = _host(params)
        out["delta_norms"] = _leaf_norms(jax.tree.map(
            np.subtract, out["params_n"], first["params0"]))
        return out

    def check(self):
        gc.collect()
        refrun = self._reference(self.first)
        got = readings(self.first, refrun)
        limits = self.cfg["limits"]
        return {k: (v, limits[k]) for k, v in got.items()}

    def calibrate(self):
        """Per seed: the program's readings, the fp8 control's and the
        half-batch fault's, each against the float32 reference, with each
        step's loss gap; the program's per-leaf gaps go to stderr. (A step
        that returns its state unchanged reads update_gap 1 and needs no
        run.)"""
        def both(run, refrun):
            out = readings(run, refrun)
            out["step_loss_gaps"] = [
                float(x) for x in readings(run, refrun, True)["loss_gap"]]
            return out

        for s in range(self.args.seed, self.args.seed + self.args.calibrate):
            t0 = time.perf_counter()
            _, first = self._first_steps(s)
            gc.collect()
            refrun = self._reference(first)
            out = {"seed": s, "program": both(first, refrun)}
            leaves = {k: [float(f"{x:.3g}") for x in v]
                      for k, v in readings(first, refrun, True).items()}
            log(json.dumps({"seed": s, "program_leaves": leaves}))
            out["control"] = both(self._reference(first, low=True), refrun)
            b = first["batches"][0]["action"].shape[1]
            half = self._reference(first, columns=np.arange(b // 2))
            out["half_batch"] = both(half, refrun)
            out["seconds"] = time.perf_counter() - t0
            print(json.dumps(out), flush=True)
