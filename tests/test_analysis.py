"""Static analyzer contract tests: every known-bad fixture must be
flagged (out-of-bounds index map, over-budget VMEM, __eq__/__hash__
retrace hazard, dead donation, stale-mesh sharding axis, unlocked
cross-thread write, leaked thread, hot-path host sync), waivers
suppress findings, and the real codebase passes clean."""

import textwrap

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.common import Finding, apply_waivers
from repro.analysis.concurrency_lint import lint_file, lint_tree
from repro.analysis.kernel_audit import (KernelLaunch, audit_kernels,
                                         audit_launch, audit_name,
                                         capture_launches)
from repro.analysis.trace_audit import (TraceEntry, audit_entry,
                                        audit_static_key, audit_traces)

_SDS = jax.ShapeDtypeStruct


def _rules(findings):
    return {f.rule for f in findings}


def _audit_pallas(fn, *args):
    records = []
    with capture_launches(records, "fixture"):
        jax.eval_shape(fn, *args)
    assert len(records) == 1
    return audit_launch(records[0])


# ---------------------------------------------------------------------------
# kernel_audit fixtures
# ---------------------------------------------------------------------------

def test_kernel_audit_flags_oob_index_map():
    """Index map walks one block past the end of the operand."""

    def bad(x):
        return pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(4,),
            in_specs=[pl.BlockSpec((32, 128), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((32, 128), lambda i: (i, 0)),
            out_shape=_SDS((128, 128), jnp.float32),
        )(x)

    findings, table = _audit_pallas(bad, _SDS((128, 128), jnp.float32))
    assert "kernel-index-map-oob" in _rules(findings)
    assert not table["ok"]


def test_kernel_audit_flags_vmem_over_budget():
    """One (2048, 4096) fp32 block is 32 MiB — double-buffered in+out
    blows the 16 MiB budget many times over."""

    def fat(x):
        return pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(1,),
            in_specs=[pl.BlockSpec((2048, 4096), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((2048, 4096), lambda i: (0, 0)),
            out_shape=_SDS((2048, 4096), jnp.float32),
        )(x)

    findings, table = _audit_pallas(fat, _SDS((2048, 4096), jnp.float32))
    assert "kernel-vmem-budget" in _rules(findings)
    assert table["vmem_total_bytes"] > 16 * 1024 * 1024


def test_kernel_audit_flags_non_dividing_block():
    launch = KernelLaunch(
        kernel="fixture", grid=(3,),
        in_specs=[pl.BlockSpec((48,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((48,), lambda i: (i,))],
        operands=[_SDS((100,), jnp.float32)],
        out_shapes=[_SDS((144,), jnp.float32)], scratch_shapes=())
    findings, _ = audit_launch(launch)
    assert "kernel-block-divisibility" in _rules(findings)


def test_kernel_audit_flags_mosaic_untiled_block():
    """A (1, 128) block over a (B, S) array: its sublane dim 1 is neither
    a multiple of 8 nor B, which Mosaic refuses although it divides."""
    kheads, bk = 4, 128
    launch = KernelLaunch(
        kernel="fixture", grid=(8 * kheads, 4),
        in_specs=[pl.BlockSpec((1, bk), lambda bh, ki: (bh // kheads, ki))],
        out_specs=[pl.BlockSpec((1, 1, bk),
                                lambda bh, ki: (bh // kheads, 0, ki))],
        operands=[_SDS((8, 512), jnp.int32)],
        out_shapes=[_SDS((8, 1, 512), jnp.int32)], scratch_shapes=())
    findings, _ = audit_launch(launch)
    assert [f.rule for f in findings] == ["kernel-block-divisibility"]
    assert "in0: block dim 0 is 1" in findings[0].message


def test_kernel_audit_flags_unnamed_launch():
    """A pallas_call without name= shows in a device trace as _kernel:
    the audit records each launch's name and flags the missing one."""

    def launch(x, name=None):
        return pl.pallas_call(
            lambda x_ref, o_ref: None, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=_SDS((8, 128), jnp.float32), name=name)(x)

    x = _SDS((8, 128), jnp.float32)
    records = []
    with capture_launches(records, "fixture"):
        jax.eval_shape(launch, x)
        jax.eval_shape(lambda x: launch(x, name="fixture"), x)
    unnamed, named = records
    assert unnamed.name is None and named.name == "fixture"
    assert _rules(audit_name(unnamed)) == {"kernel-unnamed"}
    assert audit_name(named) == []
    assert audit_launch(named)[1]["name"] == "fixture"


def test_kernel_audit_real_kernels_named():
    """Each shipped kernel launches under its own name."""
    _, tables = audit_kernels(["zamba2-2.7b"])
    assert {t["kernel"]: t["name"] for t in tables} == {
        k: k for k in ("flash_attention", "decode_attention", "ssd_chunk",
                       "vtrace", "maxpool_fwd", "maxpool_bwd")}


def test_kernel_audit_real_kernels_clean_and_complete():
    """The shipped kernels pass, and the footprint table covers every
    kernel for every audited arch."""
    findings, tables = audit_kernels(["qwen3-4b", "zamba2-2.7b"])
    assert findings == []
    for arch in ("qwen3-4b", "zamba2-2.7b"):
        kernels = {t["kernel"] for t in tables if t["arch"] == arch}
        assert kernels == {"flash_attention", "decode_attention",
                           "ssd_chunk", "vtrace", "maxpool_fwd",
                           "maxpool_bwd"}
    for t in tables:
        assert t["vmem_total_bytes"] <= t["vmem_budget_bytes"]
        assert t["roofline"]["flops"] > 0


# ---------------------------------------------------------------------------
# trace_audit fixtures
# ---------------------------------------------------------------------------

class _IdHashCfg:
    """__eq__ by value but __hash__ by identity: equal configs hash
    apart, which breaks every dict or cache keyed on them."""

    def __init__(self, d):
        self.d = d

    def __eq__(self, other):
        return isinstance(other, _IdHashCfg) and self.d == other.d

    __hash__ = object.__hash__


class _IdentityCfg:
    """Equal contents, but no value ``__eq__``: every fresh instance is a
    new jit cache key. JAX compares statics with ``__eq__`` when it looks
    up a trace, so this (not ``_IdHashCfg``) is what retraces."""

    def __init__(self, d):
        self.d = d


class _UnhashableCfg:
    def __init__(self, d):
        self.d = d

    def __eq__(self, other):           # defining __eq__ kills __hash__
        return isinstance(other, _UnhashableCfg) and self.d == other.d


def test_static_key_flags_eq_hash_mismatch():
    findings = audit_static_key(lambda: _IdHashCfg(8), "IdHashCfg")
    assert _rules(findings) == {"retrace-hazard"}
    findings = audit_static_key(lambda: _UnhashableCfg(8), "UnhashableCfg")
    assert _rules(findings) == {"retrace-hazard"}
    assert audit_static_key(lambda: (1, 2), "tuple") == []


def test_audit_entry_flags_retrace_from_id_hash_static():
    """The jit-level detector: two traces for fresh-but-equal statics."""

    def fn(x, cfg):
        return x * cfg.d

    entry = TraceEntry(
        name="fixture-retrace", fn=fn,
        make_args=lambda: ((_SDS((4,), jnp.float32),),
                           {"cfg": _IdentityCfg(3)}),
        jit_kwargs={"static_argnames": ("cfg",)})
    findings, summary = audit_entry(entry)
    assert "retrace-hazard" in _rules(findings)
    assert summary["traces"] == 2


def test_audit_entry_flags_dead_donation():
    """Donating a buffer with no (shape, dtype)-matching output."""

    def fn(big, x):
        return x + 1.0

    entry = TraceEntry(
        name="fixture-donation", fn=fn,
        make_args=lambda: ((_SDS((64, 64), jnp.float32),
                            _SDS((4,), jnp.float32)), {}),
        jit_kwargs={"donate_argnums": (0,)})
    findings, _ = audit_entry(entry)
    assert "donation-dead" in _rules(findings)


def test_audit_entry_flags_stale_mesh_axis():
    """A sharding constraint built on a mesh whose axes are not live on
    the entry's declared mesh."""
    from repro.launch.mesh import make_abstract_mesh
    live = make_abstract_mesh((2,), ("data",))
    stale = make_abstract_mesh((2,), ("model",))
    P = jax.sharding.PartitionSpec

    def fn(x):
        s = jax.sharding.NamedSharding(stale, P("model"))
        return jax.lax.with_sharding_constraint(x, s)

    entry = TraceEntry(
        name="fixture-stale-axis", fn=fn,
        make_args=lambda: ((_SDS((8, 8), jnp.float32),), {}),
        jit_kwargs={}, mesh=live)
    findings, _ = audit_entry(entry)
    assert "sharding-unknown-axis" in _rules(findings)


def test_trace_audit_real_entries_clean():
    findings, summaries = audit_traces(archs=["qwen3-4b"])
    assert findings == []
    by_name = {s["entry"]: s for s in summaries}
    assert any(n.startswith("make_lm_train_step") for n in by_name)
    assert any(n.startswith("_session_step") for n in by_name)
    for s in by_name.values():
        assert s["traces"] == 1, s


# ---------------------------------------------------------------------------
# concurrency_lint fixtures
# ---------------------------------------------------------------------------

def _lint_snippet(tmp_path, source, *, hot=None):
    path = tmp_path / "snippet.py"
    path.write_text(textwrap.dedent(source))
    return lint_file(str(path), hot=hot)


def test_lint_flags_unlocked_cross_thread_write(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import threading

        class Racy:
            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def _loop(self):
                self.count = 1 + getattr(self, "count", 0)

            def stop(self):
                self._t.join()

            def read(self):
                return self.count
        """)
    assert "thread-shared-write" in _rules(findings)
    assert "thread-no-join" not in _rules(findings)


def test_lint_lock_guard_suppresses_shared_write(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import threading

        class Locked:
            def start(self):
                self._lock = threading.Lock()
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def _loop(self):
                with self._lock:
                    self.count = 1

            def stop(self):
                self._t.join()

            def read(self):
                with self._lock:
                    return self.count
        """)
    assert "thread-shared-write" not in _rules(findings)


def test_lint_flags_thread_without_join(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import threading

        class Leaky:
            def start(self):
                self._t = threading.Thread(target=lambda: None)
                self._t.start()

            def stop(self):
                pass
        """)
    assert "thread-no-join" in _rules(findings)


def test_lint_flags_host_sync_in_hot_module(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import numpy as np
        import jax

        def hot_loop(x):
            a = x.item()
            b = np.asarray(x)
            c = jax.device_get(x)
            x.block_until_ready()
            return a, b, c
        """, hot=True)
    assert [f.rule for f in findings] == ["host-sync"] * 4


def test_waiver_suppresses_finding(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import numpy as np

        def hot_loop(x):
            return np.asarray(x)  # analysis: ignore[host-sync]
        """, hot=True)
    findings = apply_waivers(findings)
    assert len(findings) == 1 and findings[0].waived
    unrelated = apply_waivers([Finding(
        rule="other-rule", file=str(tmp_path / "snippet.py"), line=5,
        message="x")])
    assert not unrelated[0].waived       # waiver names a different rule


def test_lint_real_tree_clean():
    findings = apply_waivers(lint_tree())
    assert [f for f in findings if not f.waived] == []


# ---------------------------------------------------------------------------
# interpret-fallback stats (kernels/compat.py)
# ---------------------------------------------------------------------------

def test_resolve_interpret_counts_fallbacks():
    from repro.kernels.compat import resolve_interpret
    before = resolve_interpret.stats()
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    after = resolve_interpret.stats()
    assert after["explicit"] == before["explicit"] + 2
    resolve_interpret(None)            # CPU CI: counted, not silent
    if jax.default_backend() == "tpu":
        assert resolve_interpret.stats()["compiled"] == \
            before["compiled"] + 1
    else:
        assert resolve_interpret.stats()["fallbacks"] == \
            before["fallbacks"] + 1


# ---------------------------------------------------------------------------
# batched admission (DecodeSession.prefill_many)
# ---------------------------------------------------------------------------

def test_prefill_many_matches_prefill_into():
    """Batched admit must produce the same per-slot state and first
    tokens as N sequential prefill_into calls with the same inputs —
    and mixed prompt lengths must group into per-bucket dispatches."""
    import numpy as np

    from repro.configs import get_reduced_config
    from repro.core.generate import DecodeSession
    from repro.models import model as model_lib

    cfg = get_reduced_config("xlstm-125m")   # recurrent: exact buckets
    params, _ = model_lib.init(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    prompts = [np.array([3, 5, 7], np.int32), np.array([11], np.int32),
               np.array([2, 4], np.int32)]
    keys = list(jax.random.split(key, 3))

    def run_steps(sess, n=4):
        toks = []
        for _ in range(n):
            toks.append(sess.step()["token"][:3].copy())
        return np.stack(toks)

    a = DecodeSession(params, cfg, max_batch=4, max_len=16)
    first_a = [a.prefill_into(i, prompts[i], key=keys[i],
                              temperature=0.7) for i in range(3)]
    tokens_a = run_steps(a)

    b = DecodeSession(params, cfg, max_batch=4, max_len=16)
    first_b = b.prefill_many([0, 1, 2], prompts, keys=keys,
                             temperature=0.7)
    tokens_b = run_steps(b)

    assert list(b.active[:3]) == [True] * 3 and not b.active[3]
    for fa, fb in zip(first_a, first_b):
        assert fa.keys() == fb.keys()
        for k in fa:
            np.testing.assert_allclose(fa[k], fb[k], rtol=1e-5,
                                       atol=1e-6, err_msg=k)
    np.testing.assert_array_equal(tokens_a, tokens_b)


def test_prefill_many_rejects_bad_slots():
    import numpy as np
    import pytest

    from repro.configs import get_reduced_config
    from repro.core.generate import DecodeSession
    from repro.models import model as model_lib

    cfg = get_reduced_config("xlstm-125m")
    params, _ = model_lib.init(jax.random.PRNGKey(0), cfg)
    sess = DecodeSession(params, cfg, max_batch=2, max_len=8)
    p = [np.array([1], np.int32)] * 2
    keys = list(jax.random.split(jax.random.PRNGKey(0), 2))
    with pytest.raises(ValueError, match="duplicate"):
        sess.prefill_many([0, 0], p, keys=keys)
    sess.prefill_into(1, p[0], key=keys[0])
    with pytest.raises(ValueError, match="occupied"):
        sess.prefill_many([0, 1], p, keys=keys)
