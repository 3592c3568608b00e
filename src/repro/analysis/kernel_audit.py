"""Static Pallas kernel audit — the TPU contracts interpret-mode CI skips.

CPU CI executes every kernel in interpret mode, which checks the math but
not the launch geometry: an out-of-bounds ``BlockSpec`` index map, a block
shape that stops dividing the operand, or a VMEM working set past the
per-core budget all surface only on real hardware. This module verifies
them statically:

  * every kernel wrapper is abstract-evaluated (``jax.eval_shape``) with
    ``pl.pallas_call`` intercepted, so the audited grid / BlockSpecs /
    scratch shapes are the REAL ones the wrapper builds — nothing is
    mirrored by hand;
  * the grid is exhausted point by point and every index map evaluated
    with concrete integers (block-index convention: element offset =
    index * block dim), checking 0 <= offset and offset + block <= shape;
  * block shapes must divide the operand shape evenly — the invariant the
    kernels' ``assert``s and ``models/attention._divisor_block`` callers
    guarantee at runtime, re-proven here for the representative shapes —
    and the last two dims of a VMEM block must be multiples of (8, 128) or
    equal the operand's, as Mosaic requires;
  * every launch carries a ``name=``, so a device trace names the kernel
    (``kernel-unnamed`` otherwise: the trace names it after whichever
    jitted function encloses it);
  * the VMEM footprint is summed statically: input/output blocks counted
    TWICE (Pallas double-buffers the grid pipeline) plus scratch once,
    gated against a configurable budget (default 16 MB/v5e, per the note
    in ``kernels/flash_attention.py``). SMEM-resident operands/scratch are
    accounted separately against their own (much smaller) budget.

Each audited launch is joined with ``launch/roofline.py``'s analytic
``kernel_roofline`` numbers, so the report reads footprint and FLOPs side
by side per (kernel, arch, shape).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.common import Finding
from repro.configs import ARCHS, get_config
from repro.launch.roofline import kernel_roofline

VMEM_BUDGET_BYTES = 16 * 1024 * 1024      # v5e per-core VMEM working budget
SMEM_BUDGET_BYTES = 256 * 1024            # scalar memory: small by design
GRID_LIMIT = 2_000_000                    # defensive cap on exhaustion

AUDIT_KERNELS = ("flash_attention", "decode_attention", "ssd_chunk",
                 "vtrace", "maxpool_fwd", "maxpool_bwd")


@dataclasses.dataclass
class KernelLaunch:
    """One captured ``pl.pallas_call`` launch, fully static."""
    kernel: str
    grid: Tuple[int, ...]
    in_specs: List[Any]                   # pl.BlockSpec per operand
    out_specs: List[Any]
    operands: List[jax.ShapeDtypeStruct]  # what the kernel was called with
    out_shapes: List[jax.ShapeDtypeStruct]
    scratch_shapes: Tuple[Any, ...]       # pltpu MemoryRefs
    file: str = ""
    line: int = 0
    name: Optional[str] = None            # pallas_call's name=, if given
    operand_names: Optional[Sequence[str]] = None
    out_names: Optional[Sequence[str]] = None


@contextlib.contextmanager
def capture_launches(records: List[KernelLaunch], kernel_name: str,
                     file: str = "", line: int = 0):
    """Intercept ``pl.pallas_call``: record the launch, return abstract
    zeros of ``out_shape`` so the surrounding wrapper keeps tracing."""

    real = pl.pallas_call

    def fake(kernel, *, grid=None, in_specs=None, out_specs=None,
             out_shape=None, scratch_shapes=(), name=None, **_kw):
        def runner(*operands):
            outs_multi = isinstance(out_shape, (list, tuple))
            out_list = list(out_shape) if outs_multi else [out_shape]
            spec_list = (list(out_specs) if isinstance(out_specs,
                                                       (list, tuple))
                         else [out_specs])
            records.append(KernelLaunch(
                kernel=kernel_name,
                grid=(grid,) if isinstance(grid, int) else tuple(grid),
                in_specs=list(in_specs or []),
                out_specs=spec_list,
                operands=[jax.ShapeDtypeStruct(o.shape, o.dtype)
                          for o in operands],
                out_shapes=[jax.ShapeDtypeStruct(s.shape, s.dtype)
                            for s in out_list],
                scratch_shapes=tuple(scratch_shapes or ()),
                file=file, line=line, name=name))
            outs = [jnp.zeros(s.shape, s.dtype) for s in out_list]
            return outs if outs_multi else outs[0]
        return runner

    pl.pallas_call = fake
    try:
        yield
    finally:
        pl.pallas_call = real


def _space(ms) -> str:
    """'vmem' | 'smem' | 'any' from a pallas memory-space marker."""
    if ms is None:
        return "vmem"
    name = getattr(ms, "name", None) or str(ms)
    name = name.lower()
    if "smem" in name:
        return "smem"
    if "vmem" in name or "any" in name:
        return "vmem"
    return name


def _bytes(shape, dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * jnp.dtype(dtype).itemsize


def _iter_grid(grid: Tuple[int, ...]):
    idx = [0] * len(grid)
    total = 1
    for g in grid:
        total *= g
    for _ in range(total):
        yield tuple(idx)
        for d in range(len(grid) - 1, -1, -1):
            idx[d] += 1
            if idx[d] < grid[d]:
                break
            idx[d] = 0


def _audit_spec(launch: KernelLaunch, spec, operand, name: str,
                findings: List[Finding]) -> Dict:
    """Audit ONE (BlockSpec, operand) pair; returns its footprint row."""
    where = dict(file=launch.file, line=launch.line)
    space = _space(getattr(spec, "memory_space", None))
    block = getattr(spec, "block_shape", None)
    index_map = getattr(spec, "index_map", None)

    if block is None:               # whole operand resident (SMEM operands)
        return {"name": name, "space": space, "block_shape": None,
                "bytes": _bytes(operand.shape, operand.dtype)}

    block = tuple(operand.shape[d] if b is None else int(b)
                  for d, b in enumerate(block))
    if len(block) != len(operand.shape):
        findings.append(Finding(
            rule="kernel-block-rank", message=(
                f"{launch.kernel}/{name}: block rank {len(block)} != "
                f"operand rank {len(operand.shape)}"), **where))
        return {"name": name, "space": space, "block_shape": block,
                "bytes": _bytes(block, operand.dtype)}

    for d, (b, s) in enumerate(zip(block, operand.shape)):
        if b <= 0 or s % b != 0:
            findings.append(Finding(
                rule="kernel-block-divisibility", message=(
                    f"{launch.kernel}/{name}: block dim {d} is {b}, which "
                    f"does not divide operand dim {s} "
                    f"(shape {tuple(operand.shape)})"), **where))
    if space == "vmem":
        # Mosaic tiling: the last two block dims must be multiples of
        # (8, 128) or equal the operand's dims.
        rank = len(block)
        for d, tile in ((rank - 1, 128), (rank - 2, 8)):
            if d < 0:
                continue
            b, s = block[d], operand.shape[d]
            if b % tile and b != s:
                findings.append(Finding(
                    rule="kernel-block-divisibility", message=(
                        f"{launch.kernel}/{name}: block dim {d} is {b}, "
                        f"neither a multiple of {tile} nor the operand's "
                        f"{s} (Mosaic tiling, shape "
                        f"{tuple(operand.shape)})"), **where))

    grid_points = 1
    for g in launch.grid:
        grid_points *= g
    if grid_points > GRID_LIMIT:
        findings.append(Finding(
            rule="kernel-grid-unaudited", message=(
                f"{launch.kernel}/{name}: grid {launch.grid} has "
                f"{grid_points} points (> {GRID_LIMIT}); index maps not "
                "exhausted — shrink the representative shape"), **where))
    elif index_map is not None:
        bad = 0
        for point in _iter_grid(launch.grid):
            idx = index_map(*point)
            idx = (idx,) if not isinstance(idx, tuple) else idx
            if len(idx) != len(block):
                findings.append(Finding(
                    rule="kernel-index-map-rank", message=(
                        f"{launch.kernel}/{name}: index map returned "
                        f"{len(idx)} indices for a rank-{len(block)} "
                        f"block at grid point {point}"), **where))
                break
            for d, (ix, b, s) in enumerate(zip(idx, block, operand.shape)):
                off = int(ix) * b
                if off < 0 or off + b > s:
                    bad += 1
                    if bad == 1:
                        findings.append(Finding(
                            rule="kernel-index-map-oob", message=(
                                f"{launch.kernel}/{name}: index map walks "
                                f"out of bounds at grid point {point}: "
                                f"dim {d} block index {int(ix)} covers "
                                f"elements [{off}, {off + b}) of a "
                                f"{s}-element axis"), **where))
            if bad:
                break               # one witness per spec is enough

    return {"name": name, "space": space, "block_shape": block,
            "bytes": _bytes(block, operand.dtype)}


def audit_name(launch: KernelLaunch) -> List[Finding]:
    """``kernel-unnamed`` for a launch without ``name=``: a device trace
    then names it after whichever jitted function encloses it."""
    if launch.name:
        return []
    return [Finding(rule="kernel-unnamed", file=launch.file,
                    line=launch.line,
                    message=f"{launch.kernel}: pallas_call without name=")]


def audit_launch(launch: KernelLaunch, *,
                 vmem_budget: int = VMEM_BUDGET_BYTES,
                 smem_budget: int = SMEM_BUDGET_BYTES,
                 ) -> Tuple[List[Finding], Dict]:
    """Audit one captured launch; returns (findings, footprint table)."""
    findings: List[Finding] = []
    where = dict(file=launch.file, line=launch.line)

    rows = []
    in_names = list(launch.operand_names or []) or [
        f"in{i}" for i in range(len(launch.operands))]
    for spec, op, name in zip(launch.in_specs, launch.operands, in_names):
        rows.append(dict(_audit_spec(launch, spec, op, name, findings),
                         kind="in"))
    out_names = list(launch.out_names or []) or [
        f"out{i}" for i in range(len(launch.out_shapes))]
    for spec, op, name in zip(launch.out_specs, launch.out_shapes,
                              out_names):
        rows.append(dict(_audit_spec(launch, spec, op, name, findings),
                         kind="out"))
    for i, ref in enumerate(launch.scratch_shapes):
        rows.append({"name": f"scratch{i}", "kind": "scratch",
                     "space": _space(getattr(ref, "memory_space", None)),
                     "block_shape": tuple(ref.shape),
                     "bytes": _bytes(ref.shape, ref.dtype)})

    block_vmem = sum(r["bytes"] for r in rows
                     if r["kind"] in ("in", "out") and r["space"] == "vmem")
    scratch_vmem = sum(r["bytes"] for r in rows
                       if r["kind"] == "scratch" and r["space"] == "vmem")
    smem = sum(r["bytes"] for r in rows if r["space"] == "smem")
    # double-buffered pipeline: in/out blocks are resident twice
    vmem_total = 2 * block_vmem + scratch_vmem

    if vmem_total > vmem_budget:
        findings.append(Finding(
            rule="kernel-vmem-budget", message=(
                f"{launch.kernel}: static VMEM footprint "
                f"{vmem_total / 2**20:.2f} MiB (2x{block_vmem} block + "
                f"{scratch_vmem} scratch bytes) exceeds the "
                f"{vmem_budget / 2**20:.0f} MiB budget"), **where))
    if smem > smem_budget:
        findings.append(Finding(
            rule="kernel-smem-budget", message=(
                f"{launch.kernel}: SMEM footprint {smem} B exceeds the "
                f"{smem_budget} B budget"), **where))

    table = {
        "kernel": launch.kernel,
        "name": launch.name,
        "grid": list(launch.grid),
        "operands": rows,
        "vmem_block_bytes": block_vmem,
        "vmem_scratch_bytes": scratch_vmem,
        "vmem_total_bytes": vmem_total,
        "smem_bytes": smem,
        "vmem_budget_bytes": vmem_budget,
        "ok": not findings,
    }
    return findings, table


# ---------------------------------------------------------------------------
# representative launches per (kernel, arch config)
# ---------------------------------------------------------------------------

AUDIT_BATCH = 2          # small batch keeps grids exhaustible; seq/head
                         # dims (what the block geometry depends on) are
                         # kept at representative scale


def _unwrapped(fn):
    return inspect.unwrap(fn)


def _src(fn):
    raw = _unwrapped(fn)
    return (inspect.getsourcefile(raw) or "",
            raw.__code__.co_firstlineno)


def _flash_cases(cfg):
    from repro.kernels import flash_attention as mod
    raw = _unwrapped(mod.flash_attention)
    file, line = _src(mod.flash_attention)
    attn_mods = [m for m, _ in cfg.block_pattern if m.endswith("attn")
                 and m != "xattn"]
    window = cfg.sliding_window if attn_mods and all(
        m in ("swa_attn", "local_attn") for m in attn_mods) else 0
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    for label, b, s in (("train_4k", 1, 4096), ("serve_1k", AUDIT_BATCH,
                                                1024)):
        q = jax.ShapeDtypeStruct((b, h, s, hd), dt)
        kv = jax.ShapeDtypeStruct((b, kh, s, hd), dt)
        fn = functools.partial(raw, causal=True, window=window,
                               interpret=False)
        yield {
            "kernel": "flash_attention", "shape": label,
            "call": (fn, (q, kv, kv)), "file": file, "line": line,
            "names": (("q", "k", "v"), ("o",)),
            "roofline": dict(dtype_bytes=dt.itemsize, b=b, h=h, kh=kh, s=s,
                             hd=hd, window=window, causal=True),
        }


def _decode_cases(cfg):
    from repro.kernels import decode_attention as mod
    raw = _unwrapped(mod.decode_attention)
    file, line = _src(mod.decode_attention)
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    b, s = AUDIT_BATCH * 2, 32768          # decode_32k cache capacity
    q = jax.ShapeDtypeStruct((b, h, hd), dt)
    kv = jax.ShapeDtypeStruct((b, kh, s, hd), dt)
    slot = jax.ShapeDtypeStruct((b, s), jnp.int32)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    fn = functools.partial(raw, interpret=False)
    yield {
        "kernel": "decode_attention", "shape": "decode_32k",
        "call": (fn, (q, kv, kv, slot, pos)), "file": file, "line": line,
        "names": (("pos", "q", "k", "v", "slot_pos"), ("o",)),
        "roofline": dict(dtype_bytes=dt.itemsize, b=b, h=h, kh=kh, s=s,
                         hd=hd),
    }


def _ssd_cases(cfg):
    from repro.kernels import ssd_chunk as mod
    raw = _unwrapped(mod.ssd_chunk)
    file, line = _src(mod.ssd_chunk)
    # archs without a mamba mixer are audited at canonical SSD dims so the
    # footprint table covers every kernel for every config
    if cfg.ssm_state and cfg.ssm_head_dim:
        n, p = cfg.ssm_state, cfg.ssm_head_dim
        l = cfg.ssm_chunk
        nh = max(1, (cfg.ssm_expand * cfg.d_model) // cfg.ssm_head_dim)
        hot = any(m == "mamba" for m, _ in cfg.block_pattern)
    else:
        n, p, l, nh = 128, 64, 256, 32
        hot = False
    dt = jnp.dtype(cfg.dtype)
    bh = AUDIT_BATCH * nh
    c = jax.ShapeDtypeStruct((bh, l, n), dt)
    xdt = jax.ShapeDtypeStruct((bh, l, p), dt)
    da = jax.ShapeDtypeStruct((bh, l, 1), jnp.float32)
    h_prev = jax.ShapeDtypeStruct((bh, p, n), jnp.float32)
    fn = functools.partial(raw, interpret=False)
    yield {
        "kernel": "ssd_chunk", "shape": f"chunk_{l}",
        "call": (fn, (c, c, xdt, da, h_prev)), "file": file, "line": line,
        "names": (("c", "b", "xdt", "da", "h_prev"), ("y", "h_new")),
        "roofline": dict(dtype_bytes=4, bh=bh, l=l, n=n, p=p),
        "hot_path": hot,
    }


def _vtrace_cases(cfg):
    del cfg                                # shape is arch-independent
    from repro.kernels import vtrace as mod
    raw = _unwrapped(mod.vtrace_scan)
    file, line = _src(mod.vtrace_scan)
    t, b = 80, 1024                        # the paper's validation shape
    deltas = jax.ShapeDtypeStruct((t, b), jnp.float32)
    fn = functools.partial(raw, block_b=128, interpret=False)
    yield {
        "kernel": "vtrace", "shape": f"t{t}_b{b}",
        "call": (fn, (deltas, deltas)), "file": file, "line": line,
        "names": (("deltas", "dcs"), ("acc",)),
        "roofline": dict(t=t, b=b),
    }


def _maxpool_cases(cfg, kernel):
    """The IMPALA deep ResNet's first and last pools at the learner batch
    of T=80, B=32 (2,592 frames): 84->42 (column blocks) and 21->11 (odd,
    channel blocks)."""
    del cfg                                # shape is arch-independent
    from repro.kernels import maxpool as mod
    n = 81 * 32
    for h, c in ((84, 16), (21, 32)):
        ho = mod.pooled_size(h)
        if kernel == "maxpool_fwd":
            raw, hw = _unwrapped(mod.maxpool_fwd), {}
            args = (jax.ShapeDtypeStruct((h, h, c, n), jnp.float32),)
            names = (("x", "x_halo"), ("pooled", "index"))
        else:
            raw, hw = _unwrapped(mod.maxpool_bwd), {"hw": (h, h)}
            args = (jax.ShapeDtypeStruct((ho, ho, c, n), jnp.int8),
                    jax.ShapeDtypeStruct((ho, ho, c, n), jnp.float32))
            names = (("dy", "index", "dy_halo", "index_halo"), ("dx",))
        file, line = _src(raw)
        yield {
            "kernel": kernel, "shape": f"{h}x{h}x{c}_n{n}",
            "call": (functools.partial(raw, interpret=False, **hw), args),
            "file": file, "line": line, "names": names,
            "roofline": dict(dtype_bytes=4, h=h, w=h, c=c, n=n),
        }


_CASE_BUILDERS = {
    "flash_attention": _flash_cases,
    "decode_attention": _decode_cases,
    "ssd_chunk": _ssd_cases,
    "vtrace": _vtrace_cases,
    "maxpool_fwd": functools.partial(_maxpool_cases, kernel="maxpool_fwd"),
    "maxpool_bwd": functools.partial(_maxpool_cases, kernel="maxpool_bwd"),
}


def _has_attention(cfg) -> bool:
    mods = {m for m, _ in cfg.block_pattern}
    return bool(mods & {"attn", "local_attn", "swa_attn"}) \
        or bool(cfg.shared_attn_every)


def audit_kernels(archs: Optional[Sequence[str]] = None, *,
                  vmem_budget: int = VMEM_BUDGET_BYTES,
                  smem_budget: int = SMEM_BUDGET_BYTES,
                  ) -> Tuple[List[Finding], List[Dict]]:
    """Audit every Pallas kernel x registered arch x representative shape.

    Returns (findings, tables): one table row per audited launch, carrying
    the static footprint next to ``kernel_roofline``'s FLOP numbers.
    """
    findings: List[Finding] = []
    tables: List[Dict] = []
    for arch in archs or ARCHS:
        cfg = get_config(arch)
        for kernel in AUDIT_KERNELS:
            for case in _CASE_BUILDERS[kernel](cfg):
                fn, args = case["call"]
                records: List[KernelLaunch] = []
                with capture_launches(records, kernel,
                                      file=case["file"],
                                      line=case["line"]):
                    jax.eval_shape(fn, *args)
                if not records:
                    findings.append(Finding(
                        rule="kernel-no-launch", file=case["file"],
                        line=case["line"],
                        message=f"{kernel}[{arch}]: wrapper traced "
                                "without reaching pallas_call"))
                    continue
                for launch in records:
                    launch.operand_names, launch.out_names = case["names"]
                    fnd, table = audit_launch(
                        launch, vmem_budget=vmem_budget,
                        smem_budget=smem_budget)
                    findings.extend(fnd + audit_name(launch))
                    table.update(
                        arch=arch, shape=case["shape"],
                        hot_path=case.get(
                            "hot_path",
                            _has_attention(cfg) if "attention" in kernel
                            else True),
                        roofline=kernel_roofline(kernel,
                                                 **case["roofline"]))
                    tables.append(table)
    return findings, tables
