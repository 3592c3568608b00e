"""Static trace audit — jit-cache, donation, and sharding-axis contracts.

Every registered jitted entry point (the two LM step factories, the RL
``make_train_step``/``make_recurrent_train_step``, the decode session's
``_session_prefill``/``_session_step``, and the serving step) is
abstract-evaluated under ``jax.sharding.AbstractMesh`` + pure
``ShapeDtypeStruct``s — no devices, no FLOPs — and three contracts that
only misbehave at scale are checked statically:

  * **retrace hazard** — the entry is traced twice with *freshly
    constructed but equal* arguments (fresh structs, fresh configs from
    ``get_reduced_config``). Exactly one trace must happen; a second
    trace means some static argument hashes by identity (an
    ``__eq__``/``__hash__`` mismatch) and every caller pays a silent
    recompile per construction — the retrace storms the ROADMAP calls
    invisible on CPU CI.
  * **donation is real** — for every declared ``donate_argnums``, each
    donated leaf must find a (shape, dtype)-matching output leaf. A
    donated buffer with no matching output cannot be reused by XLA; the
    declaration silently does nothing and peak memory is double-counted.
  * **sharding axes are live** — every ``with_sharding_constraint``
    reached during the trace is intercepted and its PartitionSpec axis
    names checked against the mesh's axes (this subsumes
    ``test_sharding_spec.py``'s runtime checks as a static pass).

The module also asserts the ``session_fns`` compile cache is keyed by
config VALUE (two fresh-equal configs -> the same compiled fns object).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.common import Finding

_SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class TraceEntry:
    """One jitted entry point under audit."""
    name: str
    fn: Callable                    # the UNJITTED callable
    make_args: Callable             # () -> (args, kwargs); fresh every call
    jit_kwargs: Dict[str, Any]      # static_argnames / donate_argnums
    mesh: Any = None                # mesh whose axes constraints may name
    file: str = ""
    line: int = 0


def _where(entry: TraceEntry) -> Dict:
    return dict(file=entry.file, line=entry.line)


def _loc(fn) -> Tuple[str, int]:
    code = getattr(fn, "__code__", None)
    if code is None:
        return "", 0
    return code.co_filename, code.co_firstlineno


def _spec_axes(spec) -> set:
    axes: set = set()
    for part in tuple(spec):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        axes.update(p for p in parts if isinstance(p, str))
    return axes


@contextlib.contextmanager
def _capture_constraints(records: List[Any]):
    """Intercept ``jax.lax.with_sharding_constraint`` (every sharding
    helper resolves the attribute at call time) and record shardings."""
    real = jax.lax.with_sharding_constraint

    def spy(x, shardings, *a, **kw):
        records.extend(jax.tree.leaves(
            shardings,
            is_leaf=lambda s: isinstance(
                s, (jax.sharding.Sharding, jax.sharding.PartitionSpec))))
        return real(x, shardings, *a, **kw)

    jax.lax.with_sharding_constraint = spy
    try:
        yield
    finally:
        jax.lax.with_sharding_constraint = real


def _leaf_sig(tree) -> List[Tuple]:
    return sorted((tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
                  for leaf in jax.tree.leaves(tree)
                  if hasattr(leaf, "shape"))


def audit_static_key(make_obj: Callable, name: str,
                     file: str = "", line: int = 0) -> List[Finding]:
    """Two fresh constructions must be equal AND hash-equal: anything used
    as a jit static argument (or compile-cache key) with ``__eq__`` but an
    identity ``__hash__`` forces one retrace per construction."""
    findings: List[Finding] = []
    a, b = make_obj(), make_obj()
    try:
        ha, hb = hash(a), hash(b)
    except TypeError:
        findings.append(Finding(
            rule="retrace-hazard", file=file, line=line,
            message=f"{name}: unhashable — cannot be a jit static "
                    "argument or compile-cache key"))
        return findings
    if a == b and ha != hb:
        findings.append(Finding(
            rule="retrace-hazard", file=file, line=line,
            message=f"{name}: __eq__/__hash__ mismatch — two equal "
                    "instances hash differently, so every fresh "
                    "construction forces a recompile"))
    return findings


def audit_entry(entry: TraceEntry) -> Tuple[List[Finding], Dict]:
    """Audit one entry: trace-once, donation, sharding axes."""
    findings: List[Finding] = []
    traces = {"n": 0}

    @functools.wraps(entry.fn)
    def counted(*a, **kw):
        traces["n"] += 1
        return entry.fn(*a, **kw)

    jitted = jax.jit(counted, **entry.jit_kwargs)
    constraints: List[Any] = []
    args, kwargs = entry.make_args()
    try:
        with _capture_constraints(constraints):
            out = jitted.eval_shape(*args, **kwargs)
        args2, kwargs2 = entry.make_args()
        jitted.eval_shape(*args2, **kwargs2)
    except ValueError as e:
        if "hashable" in str(e).lower():
            findings.append(Finding(
                rule="retrace-hazard", message=(
                    f"{entry.name}: static argument is unhashable "
                    f"({e})"), **_where(entry)))
            return findings, {"entry": entry.name, "error": str(e)}
        raise

    if traces["n"] != 1:
        findings.append(Finding(
            rule="retrace-hazard", message=(
                f"{entry.name}: {traces['n']} traces for two calls with "
                "freshly-constructed-but-equal arguments — a static "
                "argument is keyed by identity, every caller recompiles"),
            **_where(entry)))

    out_sig = _leaf_sig(out)
    dead = []
    for argnum in entry.jit_kwargs.get("donate_argnums", ()) or ():
        pool = list(out_sig)
        for sig in _leaf_sig(args[argnum]):
            if sig in pool:
                pool.remove(sig)
            else:
                dead.append((argnum, sig))
    if dead:
        argnums = sorted({d[0] for d in dead})
        findings.append(Finding(
            rule="donation-dead", message=(
                f"{entry.name}: donate_argnums={argnums} donate "
                f"{len(dead)} buffer(s) with no (shape, dtype)-matching "
                "output — XLA cannot reuse them, the donation is a "
                f"silent no-op (first: {dead[0][1]})"), **_where(entry)))

    allowed = set(getattr(entry.mesh, "axis_names", ()) or ())
    used: set = set()
    for s in constraints:
        spec = getattr(s, "spec", s)
        axes = _spec_axes(spec)
        used |= axes
        s_mesh = getattr(s, "mesh", None)
        # check against the entry's LIVE mesh when one is declared — a
        # NamedSharding built on some other (stale) mesh is exactly the
        # bug this catches; fall back to the sharding's own mesh
        mesh_axes = allowed or set(
            getattr(s_mesh, "axis_names", ()) or ())
        bad = axes - mesh_axes
        if bad:
            findings.append(Finding(
                rule="sharding-unknown-axis", message=(
                    f"{entry.name}: sharding constraint names axes "
                    f"{sorted(bad)} that are not live on the mesh "
                    f"(axes: {sorted(mesh_axes)})"), **_where(entry)))

    summary = {
        "entry": entry.name,
        "traces": traces["n"],
        "donated_argnums": list(
            entry.jit_kwargs.get("donate_argnums", ()) or ()),
        "constraint_axes": sorted(used),
        "num_constraints": len(constraints),
        "ok": not findings,
    }
    return findings, summary


# ---------------------------------------------------------------------------
# the registered entry points
# ---------------------------------------------------------------------------

T, B, S = 8, 4, 32      # unroll length / batch / LM sequence (reduced)


def _abstract(tree):
    return jax.tree.map(lambda x: _SDS(x.shape, x.dtype), tree)


def _train_cfg():
    from repro.configs.base import TrainConfig
    return TrainConfig(optimizer="adamw", learning_rate=1e-3,
                       grad_clip=1.0, lr_schedule="constant")


def _lm_pieces(arch: str):
    from repro.configs import get_reduced_config
    from repro.models import model as model_lib
    from repro.optim import make_optimizer
    cfg = get_reduced_config(arch)
    opt = make_optimizer(_train_cfg())
    params = jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), cfg)[0])
    opt_state = jax.eval_shape(opt.init, params)
    return cfg, opt, params, opt_state


def _lm_batch():
    return {"tokens": _SDS((B, S + 1), jnp.int32),
            "behavior_logprob": _SDS((B, S), jnp.float32),
            "reward": _SDS((B, S), jnp.float32),
            "done": _SDS((B, S), jnp.bool_)}


def _rl_pieces(recurrent: bool):
    from repro.core import rollout as rollout_lib
    from repro.envs import catch
    from repro.models.convnet import (init_agent, minatar_lstm_net,
                                      minatar_net)
    env = catch.make()
    if recurrent:
        init_fn, apply_fn, init_state = minatar_lstm_net(env.obs_shape,
                                                         env.num_actions)
        unroll = rollout_lib.make_recurrent_unroll(env, apply_fn,
                                                   init_state, T)
    else:
        init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
        unroll = rollout_lib.make_unroll(env, apply_fn, T)
    params = jax.eval_shape(
        lambda: init_agent(init_fn, jax.random.PRNGKey(0))[0])
    key = jax.random.PRNGKey(1)
    env_state, obs = rollout_lib.env_reset_batch(env, key, B)
    carry = (unroll.initial_carry(env_state, obs, B) if recurrent
             else (env_state, obs))
    rollout = jax.eval_shape(unroll, params, _abstract(carry),
                             _SDS((2,), jnp.uint32))[1]
    return apply_fn, params, rollout


def _session_pieces(arch: str, batch: int, cache_len: int):
    from repro.configs import get_reduced_config
    from repro.core.generate import _session_prefill
    from repro.models import model as model_lib
    cfg = get_reduced_config(arch)
    params = jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), cfg)[0])
    prompt = _SDS((batch, 8), jnp.int32)
    keys = _SDS((batch, 2), jnp.uint32)
    temp = _SDS((batch,), jnp.float32)
    state = jax.eval_shape(
        functools.partial(_session_prefill, cfg=cfg,
                          cache_seq_len=cache_len),
        params, prompt, keys, temp)[0]
    return cfg, params, (prompt, keys, temp), state


def registered_entries(mesh=None) -> List[TraceEntry]:
    """Every jitted entry point the platform ships, as audit entries.

    ``mesh`` (default: a 2x2 AbstractMesh over (data, model)) scopes the
    LM factories; RL and session entries run unmeshed, exactly like the
    single-host paths.
    """
    from repro.configs import get_reduced_config
    from repro.core import generate as gen_lib
    from repro.core import learner as learner_lib
    from repro.distributed import sharding as shd
    from repro.optim import make_optimizer

    if mesh is None:
        from repro.launch.mesh import make_abstract_mesh
        mesh = make_abstract_mesh((2, 2), ("data", "model"))
    rules = shd.MEGATRON_RULES
    entries: List[TraceEntry] = []
    step_sds = _SDS((), jnp.int32)

    # -- LM step factories (2-D mesh path) ---------------------------------
    cfg, opt, params, opt_state = _lm_pieces("qwen3-4b")
    lm_rl = learner_lib.make_lm_train_step(
        cfg, opt, _train_cfg(), loss_chunk=S, mesh=mesh, rules=rules)
    file, line = _loc(lm_rl)
    entries.append(TraceEntry(
        name="make_lm_train_step[qwen3-4b]", fn=lm_rl,
        make_args=lambda: ((params, opt_state, step_sds, _lm_batch()), {}),
        jit_kwargs={"donate_argnums": (0, 1)}, mesh=mesh,
        file=file, line=line))

    lm_pre = learner_lib.make_lm_pretrain_step(
        cfg, opt, loss_chunk=S, mesh=mesh, rules=rules)
    file, line = _loc(lm_pre)
    entries.append(TraceEntry(
        name="make_lm_pretrain_step[qwen3-4b]", fn=lm_pre,
        make_args=lambda: ((params, opt_state, step_sds,
                            {"tokens": _SDS((B, S + 1), jnp.int32)}), {}),
        jit_kwargs={"donate_argnums": (0, 1)}, mesh=mesh,
        file=file, line=line))

    # -- RL learner steps ---------------------------------------------------
    tc = _train_cfg()
    apply_fn, rl_params, rollout = _rl_pieces(recurrent=False)
    rl_opt = make_optimizer(tc)
    rl_opt_state = jax.eval_shape(rl_opt.init, rl_params)
    rl_step = learner_lib.make_train_step(apply_fn, rl_opt, tc)
    file, line = _loc(rl_step)
    entries.append(TraceEntry(
        name="make_train_step[catch]", fn=rl_step,
        make_args=lambda: ((rl_params, rl_opt_state, step_sds,
                            dict(rollout)), {}),
        jit_kwargs={"donate_argnums": (0, 1)}, file=file, line=line))

    r_apply, r_params, r_rollout = _rl_pieces(recurrent=True)
    r_opt_state = jax.eval_shape(rl_opt.init, r_params)
    rec_step = learner_lib.make_recurrent_train_step(r_apply, rl_opt, tc)
    file, line = _loc(rec_step)
    entries.append(TraceEntry(
        name="make_recurrent_train_step[catch]", fn=rec_step,
        make_args=lambda: ((r_params, r_opt_state, step_sds,
                            dict(r_rollout)), {}),
        jit_kwargs={"donate_argnums": (0, 1)}, file=file, line=line))

    # -- decode session + serving step --------------------------------------
    # configs are STATIC arguments here, constructed fresh per call: this
    # is the direct fresh-equal-config retrace check.
    arch = "qwen3-4b"
    _, s_params, prefill_args, state = _session_pieces(arch, B, 64)
    file, line = _loc(gen_lib._session_prefill)
    entries.append(TraceEntry(
        name=f"_session_prefill[{arch}]", fn=gen_lib._session_prefill,
        make_args=lambda: ((s_params,) + prefill_args,
                           {"cfg": get_reduced_config(arch),
                            "cache_seq_len": 64}),
        jit_kwargs={"static_argnames": ("cfg", "cache_seq_len")},
        file=file, line=line))
    file, line = _loc(gen_lib._session_step)
    entries.append(TraceEntry(
        name=f"_session_step[{arch}]", fn=gen_lib._session_step,
        make_args=lambda: ((s_params, dict(state)),
                           {"cfg": get_reduced_config(arch)}),
        jit_kwargs={"static_argnames": ("cfg",),
                    "donate_argnums": (1,)},
        file=file, line=line))

    # serving shape: a Server's max_batch-row session (the hot loop of
    # launch/serve.py is exactly this step, donated state included)
    _, sv_params, _, sv_state = _session_pieces(arch, 8, 128)
    entries.append(TraceEntry(
        name="serve_step[max_batch=8]", fn=gen_lib._session_step,
        make_args=lambda: ((sv_params, dict(sv_state)),
                           {"cfg": get_reduced_config(arch)}),
        jit_kwargs={"static_argnames": ("cfg",),
                    "donate_argnums": (1,)},
        file=file, line=line))
    return entries


def audit_traces(mesh=None, archs: Optional[Sequence[str]] = None,
                 ) -> Tuple[List[Finding], List[Dict]]:
    """Run the full static trace audit. Returns (findings, summaries)."""
    from repro.configs import ARCHS, get_reduced_config
    from repro.core.generate import session_fns

    findings: List[Finding] = []
    summaries: List[Dict] = []

    # every registered config must be a well-behaved compile-cache key
    from repro.configs import base as cfg_base
    cfg_file = cfg_base.__file__
    for arch in archs or ARCHS:
        findings.extend(audit_static_key(
            lambda arch=arch: get_reduced_config(arch),
            f"ModelConfig[{arch}]", file=cfg_file, line=0))

    # session-fns compile cache must key by config value, not identity
    from repro.core import generate as gen_lib
    a = session_fns(get_reduced_config("qwen3-4b"))
    b = session_fns(get_reduced_config("qwen3-4b"))
    if a is not b:
        findings.append(Finding(
            rule="retrace-hazard", file=gen_lib.__file__, line=0,
            message="session_fns: two freshly-constructed equal configs "
                    "resolve to different compiled fns — the cache keys "
                    "by identity and every actor/server recompiles"))

    for entry in registered_entries(mesh):
        fnd, summary = audit_entry(entry)
        findings.extend(fnd)
        summaries.append(summary)
    return findings, summaries
