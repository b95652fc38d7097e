"""Zamba2-style hybrid backbone: Mamba-2 blocks + one parameter-shared
attention(+MLP) block applied every ``attn_every`` SSM blocks.

Layer layout for n_layers=38, attn_every=6:
  6 groups of [6 mamba blocks -> shared attn block] + 2 tail mamba blocks.
The shared block's *weights* are reused across applications (Zamba weight
sharing); each application has its own KV-cache entries at decode time.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models import transformer as tfm
from repro.models.attention import (decode_attention, group_query_heads,
                                    ungroup_heads)
from repro.models.layers import ParamDef, apply_rope, norm, rope_freqs
from repro.models.ssm import (mamba2_block_fwd, mamba2_decode_step,
                              mamba2_defs, mamba2_dims)
from repro.sharding.partition import lshard


def hybrid_layout(cfg: LMConfig) -> Tuple[int, int, int]:
    k = cfg.hybrid.attn_every
    n_groups = cfg.n_layers // k
    tail = cfg.n_layers - n_groups * k
    return n_groups, k, tail


def hybrid_defs(cfg: LMConfig) -> Dict:
    n_groups, k, tail = hybrid_layout(cfg)
    blk = mamba2_defs(cfg)
    out = {
        "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                          scale=cfg.d_model ** 0.5, dtype=cfg.dtype),
        "groups": tfm.stacked(tfm.stacked(blk, k), n_groups),
        "shared_attn": tfm.block_defs(cfg),
        "final_norm": tfm.norm_defs(cfg.d_model, cfg.norm_type),
        "unembed": ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                            dtype=cfg.dtype),
    }
    if tail:
        out["tail"] = tfm.stacked(blk, tail)
    return out


def forward(cfg: LMConfig, params: Dict, tokens: jax.Array,
            prefix_emb: Optional[jax.Array] = None, remat: bool = False,
            return_hidden: bool = False):
    x, positions = tfm.embed_tokens(cfg, params, tokens, prefix_emb)

    def mamba_body(x, bp):
        return mamba2_block_fwd(cfg, bp, x), None

    def group_body(x, gp):
        x, _ = jax.lax.scan(mamba_body, x, gp)
        x = tfm.attn_block_fwd(cfg, params["shared_attn"], x, positions)
        x, _ = tfm.ffn_block_fwd(cfg, params["shared_attn"], x)
        return x, None

    if remat:
        group_body = jax.checkpoint(group_body, prevent_cse=False)
    x, _ = jax.lax.scan(group_body, x, params["groups"])
    if "tail" in params:
        x, _ = jax.lax.scan(mamba_body, x, params["tail"])
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    if return_hidden:
        return x, jnp.zeros((), jnp.float32)
    return tfm.logits_fwd(cfg, params, x), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg: LMConfig, batch: int, max_len: int, abstract=False):
    n_groups, k, tail = hybrid_layout(cfg)
    s = cfg.ssm
    di, nh, conv_dim = mamba2_dims(cfg)
    g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.activation_dtype
    mk = (lambda sh, d: jax.ShapeDtypeStruct(sh, d)) if abstract \
        else (lambda sh, d: jnp.zeros(sh, d))
    cache = {
        "ssm_state": mk((n_groups, k, batch, nh, s.head_dim, s.d_state),
                        jnp.float32),
        "conv": mk((n_groups, k, batch, s.d_conv - 1, conv_dim), dt),
        "ak": mk((n_groups, batch, max_len, g, hd), dt),
        "av": mk((n_groups, batch, max_len, g, hd), dt),
        "pos": mk((batch,), jnp.int32),
    }
    if tail:
        cache["tail_state"] = mk((tail, batch, nh, s.head_dim, s.d_state),
                                 jnp.float32)
        cache["tail_conv"] = mk((tail, batch, s.d_conv - 1, conv_dim), dt)
    return cache


def cache_axes(cfg: LMConfig):
    n_groups, k, tail = hybrid_layout(cfg)
    ax = {
        "ssm_state": (None, None, "cache_batch", "ssm_heads", None, None),
        "conv": (None, None, "cache_batch", None, "conv_dim"),
        "ak": (None, "cache_batch", "cache_seq", "cache_kv_heads", None),
        "av": (None, "cache_batch", "cache_seq", "cache_kv_heads", None),
        "pos": ("cache_batch",),
    }
    if tail:
        ax["tail_state"] = (None, "cache_batch", "ssm_heads", None, None)
        ax["tail_conv"] = (None, "cache_batch", None, "conv_dim")
    return ax


def prefill(cfg: LMConfig, params: Dict, tokens: jax.Array,
            prefix_emb: Optional[jax.Array] = None,
            max_len: Optional[int] = None):
    x, positions = tfm.embed_tokens(cfg, params, tokens, prefix_emb)
    b, s = x.shape[0], x.shape[1]
    S = max_len or s
    n_groups, k, tail = hybrid_layout(cfg)

    def mamba_body(x, bp):
        out, st = mamba2_block_fwd(cfg, bp, x, return_state=True)
        return out, st

    def attn_apply(x):
        bp = params["shared_attn"]
        h = norm(x, bp["attn_norm"], cfg.norm_type, cfg.norm_eps)
        h = lshard(h, "act_batch", "act_seq", "act_embed")
        q, kk, vv = tfm._qkv(cfg, bp["attn"], h, positions)
        qg = group_query_heads(q, cfg.n_kv_heads)
        from repro.models.attention import chunked_attention
        o = chunked_attention(qg, kk, vv, causal=True, q_chunk=cfg.q_chunk,
                              kv_chunk=cfg.kv_chunk,
                              block_skip=cfg.causal_block_skip)
        o = jnp.einsum("bshk,hkd->bsd", ungroup_heads(o), bp["attn"]["wo"])
        x = x + lshard(o, "act_batch", "act_res_seq", "act_embed")
        x, _ = tfm.ffn_block_fwd(cfg, bp, x)
        if S > s:
            pad = [(0, 0), (0, S - s), (0, 0), (0, 0)]
            kk, vv = jnp.pad(kk, pad), jnp.pad(vv, pad)
        return x, kk, vv

    def group_body(x, gp):
        x, sts = jax.lax.scan(mamba_body, x, gp)
        x, kk, vv = attn_apply(x)
        return x, (sts, kk, vv)

    x, (g_states, ks, vs) = jax.lax.scan(group_body, x, params["groups"])
    cache = {
        "ssm_state": g_states[0], "conv": g_states[1],
        "ak": ks, "av": vs, "pos": jnp.full((b,), s, jnp.int32),
    }
    if "tail" in params:
        x, t_states = jax.lax.scan(mamba_body, x, params["tail"])
        cache["tail_state"], cache["tail_conv"] = t_states
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    return tfm.logits_fwd(cfg, params, x[:, -1:, :]), cache


def decode_step(cfg: LMConfig, params: Dict, cache: Dict, tokens: jax.Array):
    b = tokens.shape[0]
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = lshard(x, "act_batch", "act_res_seq", "act_embed")
    positions = pos[:, None]
    inv, rot = rope_freqs(cfg.resolved_head_dim, cfg.rope_fraction,
                          cfg.rope_theta)

    def mamba_body(x, inp):
        bp, st, cb = inp
        out, st, cb = mamba2_decode_step(cfg, bp, x, st, cb)
        return out, (st, cb)

    def attn_apply(x, k_cache, v_cache):
        bp = params["shared_attn"]
        h = norm(x, bp["attn_norm"], cfg.norm_type, cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wq"])
        kk = jnp.einsum("bsd,dgk->bsgk", h, bp["attn"]["wk"])
        vv = jnp.einsum("bsd,dgk->bsgk", h, bp["attn"]["wv"])
        if cfg.pos_emb == "rope":
            q = apply_rope(q, positions, inv, rot)
            kk = apply_rope(kk, positions, inv, rot)
        upd = lambda c, new: jax.vmap(
            lambda cb_, nb, pb: jax.lax.dynamic_update_slice_in_dim(
                cb_, nb, pb, axis=0))(c, new, pos)
        k_cache, v_cache = upd(k_cache, kk), upd(v_cache, vv)
        k_cache = lshard(k_cache, "cache_batch", "cache_seq",
                         "cache_kv_heads", None)
        v_cache = lshard(v_cache, "cache_batch", "cache_seq",
                         "cache_kv_heads", None)
        qg = group_query_heads(q, cfg.n_kv_heads)
        o = decode_attention(qg, k_cache.reshape(*k_cache.shape[:2], -1),
                             v_cache.reshape(*v_cache.shape[:2], -1), pos + 1)
        o = jnp.einsum("bshk,hkd->bsd", ungroup_heads(o), bp["attn"]["wo"])
        x = x + o
        x, _ = tfm.ffn_block_fwd(cfg, bp, x)
        return x, k_cache, v_cache

    def group_body(x, inp):
        gp, sts, cbs, kc, vc = inp
        x, st = jax.lax.scan(mamba_body, x, (gp, sts, cbs))
        x, kc, vc = attn_apply(x, kc, vc)
        return x, (st[0], st[1], kc, vc)

    x, (sst, scv, ks, vs) = jax.lax.scan(
        group_body, x, (params["groups"], cache["ssm_state"], cache["conv"],
                        cache["ak"], cache["av"]))
    new = {"ssm_state": sst, "conv": scv, "ak": ks, "av": vs, "pos": pos + 1}
    if "tail" in params:
        x, t = jax.lax.scan(mamba_body, x,
                            (params["tail"], cache["tail_state"],
                             cache["tail_conv"]))
        new["tail_state"], new["tail_conv"] = t
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    return tfm.logits_fwd(cfg, params, x), new
