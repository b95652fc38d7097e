"""Unified model API over all architecture families.

Functions are family-dispatched but share one signature so the scheduler,
trainer, server, dry-run and benchmarks are architecture-agnostic:

    defs   = param_defs(cfg)                 # ParamDef tree (shapes + axes)
    params = init_params(cfg, key)
    logits, aux = forward(cfg, params, tokens, prefix_emb, remat=...)
    logits, cache = prefill(cfg, params, tokens, prefix_emb, max_len=...)
    logits, cache = decode_step(cfg, params, cache, tokens)
    generated, cache = decode_loop(cfg, params, cache, tokens, steps)
    cache  = init_cache(cfg, batch, max_len, abstract=...)
    specs  = input_specs(cfg, shape)         # ShapeDtypeStruct stand-ins
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig, ShapeSuite
from repro.models import hybrid as hyb
from repro.models import ssm as ssm_lib
from repro.models import transformer as tfm
from repro.models.layers import (ParamDef, abstract_from_defs, axes_from_defs,
                                 init_from_defs, norm)

ATTN_FAMILIES = ("dense", "vlm", "audio", "moe")


# ---------------------------------------------------------------------------
# xLSTM model assembly (blocks live in models/ssm.py)
# ---------------------------------------------------------------------------

def _xlstm_layout(cfg: LMConfig):
    every = cfg.xlstm.slstm_every
    assert cfg.n_layers % every == 0, (cfg.n_layers, every)
    return cfg.n_layers // every, every - 1   # (n_pairs, mlstm_per_pair)


def _xlstm_defs(cfg: LMConfig) -> Dict:
    n_pairs, n_m = _xlstm_layout(cfg)
    return {
        "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                          scale=cfg.d_model ** 0.5, dtype=cfg.dtype),
        "m": tfm.stacked(tfm.stacked(ssm_lib.mlstm_defs(cfg), n_m), n_pairs),
        "s": tfm.stacked(ssm_lib.slstm_defs(cfg), n_pairs),
        "final_norm": tfm.norm_defs(cfg.d_model, cfg.norm_type),
        "unembed": ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                            dtype=cfg.dtype),
    }


def _xlstm_forward(cfg, params, tokens, prefix_emb=None, remat=False,
                   return_hidden=False):
    x, _ = tfm.embed_tokens(cfg, params, tokens, prefix_emb)

    def pair_body(x, pp):
        mp, sp = pp

        def m_body(x, bp):
            return ssm_lib.mlstm_block_fwd(cfg, bp, x), None

        x, _ = jax.lax.scan(m_body, x, mp)
        x = ssm_lib.slstm_block_fwd(cfg, sp, x)
        return x, None

    if remat:
        pair_body = jax.checkpoint(pair_body, prevent_cse=False)
    x, _ = jax.lax.scan(pair_body, x, (params["m"], params["s"]))
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    if return_hidden:
        return x, jnp.zeros((), jnp.float32)
    return tfm.logits_fwd(cfg, params, x), jnp.zeros((), jnp.float32)


def _xlstm_init_cache(cfg, batch, max_len, abstract=False):
    n_pairs, n_m = _xlstm_layout(cfg)
    di, nh, dh = ssm_lib.xlstm_dims(cfg)
    dh_s = cfg.d_model // cfg.n_heads
    mk = (lambda sh, d: jax.ShapeDtypeStruct(sh, d)) if abstract \
        else (lambda sh, d: jnp.zeros(sh, d))
    neg = (lambda sh, d: jax.ShapeDtypeStruct(sh, d)) if abstract \
        else (lambda sh, d: jnp.full(sh, -jnp.inf, d))
    return {
        "mC": mk((n_pairs, n_m, batch, nh, dh, dh), jnp.float32),
        "mn": mk((n_pairs, n_m, batch, nh, dh), jnp.float32),
        "mm": neg((n_pairs, n_m, batch, nh), jnp.float32),
        "sc": mk((n_pairs, batch, cfg.n_heads, dh_s), jnp.float32),
        "sn": mk((n_pairs, batch, cfg.n_heads, dh_s), jnp.float32),
        "sh": mk((n_pairs, batch, cfg.n_heads, dh_s), jnp.float32),
        "sm": neg((n_pairs, batch, cfg.n_heads, dh_s), jnp.float32),
        "pos": mk((batch,), jnp.int32),
    }


def _xlstm_cache_axes(cfg):
    return {
        "mC": (None, None, "cache_batch", "ssm_heads", None, None),
        "mn": (None, None, "cache_batch", "ssm_heads", None),
        "mm": (None, None, "cache_batch", "ssm_heads"),
        "sc": (None, "cache_batch", "ssm_heads", None),
        "sn": (None, "cache_batch", "ssm_heads", None),
        "sh": (None, "cache_batch", "ssm_heads", None),
        "sm": (None, "cache_batch", "ssm_heads", None),
        "pos": ("cache_batch",),
    }


def _xlstm_prefill(cfg, params, tokens, prefix_emb=None, max_len=None):
    x, _ = tfm.embed_tokens(cfg, params, tokens, prefix_emb)
    b, s = x.shape[0], x.shape[1]

    def pair_body(x, pp):
        mp, sp = pp

        def m_body(x, bp):
            x, st = ssm_lib.mlstm_block_fwd(cfg, bp, x, return_state=True)
            return x, st

        x, mst = jax.lax.scan(m_body, x, mp)
        x, sst = ssm_lib.slstm_block_fwd(cfg, sp, x, return_state=True)
        return x, (mst, sst)

    x, (mst, sst) = jax.lax.scan(pair_body, x, (params["m"], params["s"]))
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    cache = {"mC": mst[0], "mn": mst[1], "mm": mst[2],
             "sc": sst[0], "sn": sst[1], "sh": sst[2], "sm": sst[3],
             "pos": jnp.full((b,), s, jnp.int32)}
    return tfm.logits_fwd(cfg, params, x[:, -1:, :]), cache


def _xlstm_decode(cfg, params, cache, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)

    def pair_body(x, inp):
        mp, sp, mC, mn, mm, sc, sn, sh, sm = inp

        def m_body(x, minp):
            bp, C, n, m_ = minp
            x, st = ssm_lib.mlstm_decode_step(cfg, bp, x, (C, n, m_))
            return x, st

        x, mst = jax.lax.scan(m_body, x, (mp, mC, mn, mm))
        x, sst = ssm_lib.slstm_decode_step(cfg, sp, x, (sc, sn, sh, sm))
        return x, (mst, sst)

    x, (mst, sst) = jax.lax.scan(
        pair_body, x, (params["m"], params["s"], cache["mC"], cache["mn"],
                       cache["mm"], cache["sc"], cache["sn"], cache["sh"],
                       cache["sm"]))
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    new = {"mC": mst[0], "mn": mst[1], "mm": mst[2],
           "sc": sst[0], "sn": sst[1], "sh": sst[2], "sm": sst[3],
           "pos": cache["pos"] + 1}
    return tfm.logits_fwd(cfg, params, x), new


# ---------------------------------------------------------------------------
# dispatch tables
# ---------------------------------------------------------------------------

def param_defs(cfg: LMConfig) -> Dict:
    if cfg.family in ATTN_FAMILIES:
        return tfm.transformer_defs(cfg)
    if cfg.family == "ssm":
        return _xlstm_defs(cfg)
    if cfg.family == "hybrid":
        return hyb.hybrid_defs(cfg)
    raise ValueError(cfg.family)


def init_params(cfg: LMConfig, key: jax.Array):
    return init_from_defs(param_defs(cfg), key)


def abstract_params(cfg: LMConfig):
    return abstract_from_defs(param_defs(cfg))


def param_axes(cfg: LMConfig):
    return axes_from_defs(param_defs(cfg))


def forward(cfg: LMConfig, params, tokens, prefix_emb=None, remat=False,
            return_hidden=False):
    if cfg.family in ATTN_FAMILIES:
        return tfm.forward(cfg, params, tokens, prefix_emb, remat,
                           return_hidden)
    if cfg.family == "ssm":
        return _xlstm_forward(cfg, params, tokens, prefix_emb, remat,
                              return_hidden)
    if cfg.family == "hybrid":
        return hyb.forward(cfg, params, tokens, prefix_emb, remat,
                           return_hidden)
    raise ValueError(cfg.family)


def unembed_weight(cfg: LMConfig, params):
    """The output head: hidden @ unembed_weight gives the logits."""
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return w / cfg.logits_scaling if cfg.logits_scaling != 1.0 else w


def prefill(cfg: LMConfig, params, tokens, prefix_emb=None, max_len=None):
    if cfg.family in ATTN_FAMILIES:
        return tfm.prefill(cfg, params, tokens, prefix_emb, max_len)
    if cfg.family == "ssm":
        return _xlstm_prefill(cfg, params, tokens, prefix_emb, max_len)
    if cfg.family == "hybrid":
        return hyb.prefill(cfg, params, tokens, prefix_emb, max_len)
    raise ValueError(cfg.family)


def decode_step(cfg: LMConfig, params, cache, tokens):
    if cfg.family in ATTN_FAMILIES:
        return tfm.decode_step(cfg, params, cache, tokens)
    if cfg.family == "ssm":
        return _xlstm_decode(cfg, params, cache, tokens)
    if cfg.family == "hybrid":
        return hyb.decode_step(cfg, params, cache, tokens)
    raise ValueError(cfg.family)


def decode_loop(cfg: LMConfig, params, cache, tokens, steps: int):
    """``steps`` greedy decode steps in one program. tokens: (b, 1), the
    token the first step reads. Returns (generated (b, steps) int32,
    cache).

    The cache rides in the loop's carry, as it rides in each step's layer
    loop, so a donated cache is updated in place by every step.
    """
    def body(carry, _):
        cache, tok = carry
        logits, cache = decode_step(cfg, params, cache, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        return (cache, tok), tok[:, 0]

    (cache, _), generated = jax.lax.scan(body, (cache, tokens), None,
                                         length=steps)
    return generated.T, cache


def init_cache(cfg: LMConfig, batch: int, max_len: int, abstract=False):
    if cfg.family in ATTN_FAMILIES:
        return tfm.init_cache(cfg, batch, max_len, abstract)
    if cfg.family == "ssm":
        return _xlstm_init_cache(cfg, batch, max_len, abstract)
    if cfg.family == "hybrid":
        return hyb.init_cache(cfg, batch, max_len, abstract)
    raise ValueError(cfg.family)


def cache_axes(cfg: LMConfig):
    if cfg.family in ATTN_FAMILIES:
        return tfm.cache_axes(cfg)
    if cfg.family == "ssm":
        return _xlstm_cache_axes(cfg)
    if cfg.family == "hybrid":
        return hyb.cache_axes(cfg)
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — never allocates)
# ---------------------------------------------------------------------------

def text_len(cfg: LMConfig, shape: ShapeSuite) -> int:
    return shape.seq_len - cfg.prefix_len


def input_specs(cfg: LMConfig, shape: ShapeSuite) -> Dict:
    """Abstract inputs for one (arch × shape) dry-run cell."""
    B = shape.global_batch
    i32 = jnp.int32
    if shape.kind == "train":
        s = text_len(cfg, shape)
        specs = {"tokens": jax.ShapeDtypeStruct((B, s), i32),
                 "labels": jax.ShapeDtypeStruct((B, s), i32)}
    elif shape.kind == "prefill":
        s = text_len(cfg, shape)
        specs = {"tokens": jax.ShapeDtypeStruct((B, s), i32)}
    else:  # decode / long_decode: one new token against a seq_len cache
        specs = {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                 "cache": init_cache(cfg, B, shape.seq_len, abstract=True)}
    if cfg.prefix_len and shape.kind in ("train", "prefill"):
        specs["prefix_emb"] = jax.ShapeDtypeStruct(
            (B, cfg.prefix_len, cfg.d_model), cfg.activation_dtype)
    return specs


def input_axes(cfg: LMConfig, shape: ShapeSuite) -> Dict:
    """Logical sharding axes matching :func:`input_specs`."""
    if shape.kind in ("train", "prefill"):
        axes = {"tokens": ("act_batch", "act_seq")}
        if shape.kind == "train":
            axes["labels"] = ("act_batch", "act_seq")
        if cfg.prefix_len:
            axes["prefix_emb"] = ("act_batch", "act_seq", "act_embed")
        return axes
    return {"tokens": ("act_batch", None), "cache": cache_axes(cfg)}
