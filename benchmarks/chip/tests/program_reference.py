"""A test-only stand-in for a serving cell's plain reference: the
program's own model, in float32 at ``HIGHEST``, with plain attention and
chunk scan. It checks the chat runner's comparison on the CPU; a cell's
own reference (reference/<name>.py) imports nothing of the program.

``weights`` runs the program's initialisation from the key and keeps
every matrix in the model's compute type (bfloat16 at published widths,
as a server holds them). ``logits`` gives the float32 logits of a full
forward over right-padded rows: ``model.forward``'s embedding, groups
and final norm, one group per call, so that only one group's weights are
ever held in float32. With ``low`` it gives the control: every matrix
rounded to float8 e4m3 with one scale a tensor.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chip.runners.serve_chat import model_config


def weights(key, layout, config):
    from repro.models import model as model_lib
    del layout
    cfg = model_config(config)

    def make(k):
        params = model_lib.init(k, cfg)[0]
        return jax.tree.map(lambda w: w.astype(cfg.dtype) if w.ndim >= 2
                            else w, params)
    return jax.jit(make)(key)


def _f32(tree, low):
    def one(w):
        w = w.astype(jnp.float32)
        if not low or w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w)) / 448.0 + 1e-30
        return (w / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    return jax.tree.map(one, tree)


@functools.partial(jax.jit, static_argnames=("cfg", "low"))
def _embed(embed, tokens, *, cfg, low):
    from repro.models import model as model_lib
    return model_lib._embed({"embed": _f32(embed, low)}, cfg, tokens,
                            jnp.arange(tokens.shape[1]))


@functools.partial(jax.jit, static_argnames=("cfg", "low"))
def _group(block, shared, x, *, cfg, low):
    from repro.models import blocks
    from repro.models.model import SHARED_PATTERN
    pos = jnp.arange(x.shape[1])
    with jax.default_matmul_precision("highest"):
        x, _, _ = blocks.block_apply(_f32(block, low), x, cfg=cfg,
                                     positions=pos, dtype=x.dtype)
        if shared is not None:
            x, _, _ = blocks.block_apply(_f32(shared, low), x, cfg=cfg,
                                         positions=pos,
                                         pattern=SHARED_PATTERN,
                                         dtype=x.dtype)
    return x


@functools.partial(jax.jit, static_argnames=("cfg", "low"))
def _head(head, x, *, cfg, low):
    from repro.models import model as model_lib
    from repro.models.common import make_norm
    head = _f32(head, low)
    with jax.default_matmul_precision("highest"):
        x = make_norm(cfg)[1](head["final_norm"], x)
        return model_lib.logits_from_hidden(head, cfg, x)


def logits(params, tokens, config, low=False):
    cfg = dataclasses.replace(model_config(config), dtype="float32",
                              attn_impl="xla", ssd_impl="xla", remat=False)
    low = bool(low)
    x = _embed(params["embed"], tokens, cfg=cfg, low=low)
    for g in range(cfg.num_groups):
        block = jax.tree.map(lambda a: a[g], params["blocks"])
        x = _group(block, params.get("shared"), x, cfg=cfg, low=low)
    head = {k: params[k] for k in ("embed", "unembed", "final_norm")
            if k in params}
    return _head(head, x, cfg=cfg, low=low)
