"""Decoder-only transformer backbone (dense / vlm / audio / moe families).

Layers are stacked and iterated with ``jax.lax.scan`` so the lowered HLO stays
small at 512 partitions (the HLO-walking cost model in ``benchmarks.hlo_cost``
scales loop-body costs by trip count for the roofline).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models import moe as moe_lib
from repro.models.attention import (chunked_attention, decode_attention,
                                    group_query_heads, ungroup_heads)
from repro.models.layers import (ParamDef, apply_rope, mlp_defs, mlp_fwd,
                                 norm, norm_defs, rope_freqs)
from repro.sharding.partition import lshard, per_shard
from repro.sharding.rules import Packed


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def attn_defs(cfg: LMConfig) -> Dict[str, ParamDef]:
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.dtype
    return {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim"), dtype=dt),
        "wk": ParamDef((d, g, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wv": ParamDef((d, g, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"), dtype=dt),
    }


def block_defs(cfg: LMConfig) -> Dict:
    out = {
        "attn": attn_defs(cfg),
        "attn_norm": norm_defs(cfg.d_model, cfg.norm_type),
        "mlp_norm": norm_defs(cfg.d_model, cfg.norm_type),
    }
    if cfg.moe:
        out["moe"] = moe_lib.moe_defs(cfg)
    else:
        out["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.dtype)
    return out


def stacked(defs, n: int):
    """Stack per-layer ParamDefs along a leading `layers` axis."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.init,
                           d.scale, d.dtype),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def transformer_defs(cfg: LMConfig) -> Dict:
    d = cfg.d_model
    out = {
        "embed": ParamDef((cfg.vocab, d), ("vocab", "embed"), scale=d ** 0.5,
                          dtype=cfg.dtype),
        "blocks": stacked(block_defs(cfg), cfg.n_layers),
        "final_norm": norm_defs(d, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        out["unembed"] = ParamDef((d, cfg.vocab), ("embed", "vocab"),
                                  dtype=cfg.dtype)
    if cfg.pos_emb == "learned":
        out["pos_emb"] = ParamDef((cfg.max_seq_len, d), ("pos", "embed"),
                                  dtype=cfg.dtype)
    return out


# ---------------------------------------------------------------------------
# forward pieces
# ---------------------------------------------------------------------------

def _qkv(cfg: LMConfig, p: Dict, h: jax.Array, positions: jax.Array):
    inv, rot = rope_freqs(cfg.resolved_head_dim, cfg.rope_fraction,
                          cfg.rope_theta)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", h, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", h, p["wv"])
    q = lshard(q, "act_batch", "act_seq", "act_heads", None)
    k = lshard(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = lshard(v, "act_batch", "act_seq", "act_kv_heads", None)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, inv, rot)
        k = apply_rope(k, positions, inv, rot)
    return q, k, v


def attend(cfg: LMConfig, p: Dict, h: jax.Array, positions: jax.Array):
    """Causal self-attention over whole prompts. h: (b, s, d), normed.
    Returns the output projected back to (b, s, d), and the keys and
    values, (b, s, n_kv_heads, head_dim)."""
    q, k, v = _qkv(cfg, p, h, positions)
    qg = group_query_heads(q, cfg.n_kv_heads)
    o = chunked_attention(qg, k, v, causal=True, q_chunk=cfg.q_chunk,
                          kv_chunk=cfg.kv_chunk,
                          block_skip=cfg.causal_block_skip,
                          scale=cfg.attn_scale)
    return jnp.einsum("bshk,hkd->bsd", ungroup_heads(o), p["wo"]), k, v


def pack_kv(cfg: LMConfig, kv: jax.Array, max_len: int) -> jax.Array:
    """One layer's keys or values, (b, s, n_kv_heads, head_dim), in the
    cache's layout: (b, max_len, n_kv_heads * head_dim), zero past s."""
    b, s = kv.shape[:2]
    kv = kv.reshape(b, s, -1)
    if max_len > s:
        kv = jnp.pad(kv, [(0, 0), (0, max_len - s), (0, 0)])
    return lshard(kv, "cache_batch", "cache_seq", _kv_axis(cfg))


def attn_block_fwd(cfg: LMConfig, p: Dict, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    h = norm(x, p["attn_norm"], cfg.norm_type, cfg.norm_eps)
    # SP boundary: re-gather the sequence on the bf16 normed tensor, BEFORE
    # the projections — otherwise GSPMD resolves the reshard as an fp32
    # all-reduce after the dots (measured 2.7 GB/layer; EXPERIMENTS §Perf)
    h = lshard(h, "act_batch", "act_seq", "act_embed")
    q, k, v = _qkv(cfg, p["attn"], h, positions)
    qg = group_query_heads(q, cfg.n_kv_heads)
    s = qg.shape[1]
    if cfg.attn_custom_vjp and s % min(cfg.q_chunk, s) == 0 \
            and k.shape[1] % min(cfg.kv_chunk, k.shape[1]) == 0:
        from repro.models.attention import flash_attention_jax
        o = flash_attention_jax(qg, k, v, True, cfg.q_chunk, cfg.kv_chunk)
    else:
        o = chunked_attention(qg, k, v, causal=True, q_chunk=cfg.q_chunk,
                              kv_chunk=cfg.kv_chunk,
                              block_skip=cfg.causal_block_skip,
                              scale=cfg.attn_scale)
    o = ungroup_heads(o)
    o = jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
    return x + lshard(o, "act_batch", "act_res_seq", "act_embed")


def ffn_block_fwd(cfg: LMConfig, p: Dict, x: jax.Array) \
        -> Tuple[jax.Array, jax.Array]:
    h = norm(x, p["mlp_norm"], cfg.norm_type, cfg.norm_eps)
    h = lshard(h, "act_batch", "act_seq", "act_embed")   # bf16 SP boundary
    if cfg.moe:
        y, aux = moe_lib.moe_fwd(cfg, p["moe"], h)
    else:
        y, aux = mlp_fwd(p["mlp"], h, cfg.act, cfg.gated_mlp), jnp.zeros((), jnp.float32)
    return x + y, aux


def block_fwd(cfg: LMConfig, p: Dict, x: jax.Array, positions: jax.Array):
    x = attn_block_fwd(cfg, p, x, positions)
    return ffn_block_fwd(cfg, p, x)


# ---------------------------------------------------------------------------
# embedding / logits
# ---------------------------------------------------------------------------

def embed_rows(cfg: LMConfig, params: Dict, tokens: jax.Array) -> jax.Array:
    """The tokens' embedding rows, times the embedding multiplier."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def embed_tokens(cfg: LMConfig, params: Dict, tokens: jax.Array,
                 prefix_emb: Optional[jax.Array], pos0: int = 0):
    x = embed_rows(cfg, params, tokens)
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    positions = pos0 + jnp.arange(s)[None, :]
    if cfg.pos_emb == "learned":
        pe = jax.lax.dynamic_slice_in_dim(params["pos_emb"], pos0, s, axis=0)
        x = x + pe[None]
    x = lshard(x, "act_batch", "act_res_seq", "act_embed")
    return x, positions


def logits_fwd(cfg: LMConfig, params: Dict, x: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return lshard(logits, "act_batch", "act_seq", "act_vocab")


# ---------------------------------------------------------------------------
# full forward (train / prefill / decode)
# ---------------------------------------------------------------------------

def forward(cfg: LMConfig, params: Dict, tokens: jax.Array,
            prefix_emb: Optional[jax.Array] = None,
            remat: bool = False,
            return_hidden: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Training/scoring forward. Returns (logits|hidden, aux_loss)."""
    x, positions = embed_tokens(cfg, params, tokens, prefix_emb)

    def body(carry, bp):
        x, aux = carry
        x, a = block_fwd(cfg, bp, x, positions)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    if return_hidden:
        return x, aux
    return logits_fwd(cfg, params, x), aux


def prefill(cfg: LMConfig, params: Dict, tokens: jax.Array,
            prefix_emb: Optional[jax.Array] = None,
            max_len: Optional[int] = None):
    """Forward + KV-cache emission. Returns (logits, cache)."""
    x, positions = embed_tokens(cfg, params, tokens, prefix_emb)
    b, s = x.shape[0], x.shape[1]
    S = max_len or s

    def body(x, bp):
        h = norm(x, bp["attn_norm"], cfg.norm_type, cfg.norm_eps)
        h = lshard(h, "act_batch", "act_seq", "act_embed")
        o, k, v = attend(cfg, bp["attn"], h, positions)
        x = x + lshard(o, "act_batch", "act_res_seq", "act_embed")
        x, _ = ffn_block_fwd(cfg, bp, x)
        return x, (pack_kv(cfg, k, S), pack_kv(cfg, v, S))

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = logits_fwd(cfg, params, x[:, -1:, :])
    cache = {"k": ks, "v": vs,
             "pos": jnp.full((b,), s, jnp.int32)}
    return logits, cache


def init_cache(cfg: LMConfig, batch: int, max_len: int, abstract: bool = False):
    """Keys and values as (layers, batch, max_len, n_kv_heads * head_dim).

    Heads and head_dim share one minor axis: with a head_dim below the
    TPU's 128 lanes a separate head_dim axis is stored sequence-minor, and
    every layer's slice is then relaid out to be read and written.
    """
    shape = (cfg.n_layers, batch, max_len,
             cfg.n_kv_heads * cfg.resolved_head_dim)
    dt = cfg.activation_dtype
    if abstract:
        mk = lambda s, d: jax.ShapeDtypeStruct(s, d)
    else:
        mk = lambda s, d: jnp.zeros(s, d)
    return {"k": mk(shape, dt), "v": mk(shape, dt),
            "pos": mk((batch,), jnp.int32)}


def _kv_axis(cfg: LMConfig) -> Packed:
    """The cache's packed axis shards whole kv heads only."""
    return Packed("cache_kv_heads", cfg.n_kv_heads)


def cache_axes(cfg: LMConfig):
    ax = ("layers", "cache_batch", "cache_seq", _kv_axis(cfg))
    return {"k": ax, "v": ax, "pos": ("cache_batch",)}


def write_kv(cfg: LMConfig, cache: jax.Array, new: jax.Array, i,
             pos: jax.Array) -> jax.Array:
    """Layer ``i``'s new keys or values, (b, n_kv_heads * head_dim), into
    the stacked cache: one write per row, at the row's own position, on
    each shard's own rows and heads under a mesh."""
    ax = ("layers", "cache_batch", None, _kv_axis(cfg))
    put = lambda c, n, layer, p: c.at[layer, jnp.arange(c.shape[1]), p].set(n)
    return per_shard(put, (ax, ("cache_batch", _kv_axis(cfg)), None,
                           ("cache_batch",)), ax)(cache, new, i, pos)


def layer_kv(cfg: LMConfig, cache: jax.Array, i) -> jax.Array:
    """Layer ``i`` of the stacked cache, (b, S, n_kv_heads * head_dim)."""
    c = jax.lax.dynamic_index_in_dim(cache, i, 0, keepdims=False)
    return lshard(c, "cache_batch", "cache_seq", _kv_axis(cfg))


def attend_step(cfg: LMConfig, p: Dict, h: jax.Array, ck: jax.Array,
                cv: jax.Array, i, pos: jax.Array):
    """One new position of attention layer ``i``. h: (b, 1, d), normed;
    ck, cv: the stacked caches; pos: (b,) each row's position. Writes the
    new key and value at ``pos``, attends over positions 0..pos, and
    returns (output (b, 1, d), ck, cv)."""
    b = h.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", h, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", h, p["wv"])
    if cfg.pos_emb == "rope":
        inv, rot = rope_freqs(cfg.resolved_head_dim, cfg.rope_fraction,
                              cfg.rope_theta)
        q = apply_rope(q, pos[:, None], inv, rot)
        k = apply_rope(k, pos[:, None], inv, rot)
    ck = write_kv(cfg, ck, k.reshape(b, -1), i, pos)
    cv = write_kv(cfg, cv, v.reshape(b, -1), i, pos)
    qg = group_query_heads(q, cfg.n_kv_heads)
    o = decode_attention(qg, layer_kv(cfg, ck, i), layer_kv(cfg, cv, i),
                         pos + 1, scale=cfg.attn_scale)
    return jnp.einsum("bshk,hkd->bsd", ungroup_heads(o), p["wo"]), ck, cv


def decode_step(cfg: LMConfig, params: Dict, cache: Dict, tokens: jax.Array):
    """One decode step. tokens: (b, 1). Returns (logits, new_cache).

    The stacked cache rides in the layer loop's carry and each layer writes
    only its row's new position, so a donated cache is updated in place.
    """
    pos = cache["pos"]                                   # (b,)
    x = embed_rows(cfg, params, tokens)                  # (b, 1, d)
    if cfg.pos_emb == "learned":
        pe = jnp.take(params["pos_emb"], pos, axis=0)[:, None, :]
        x = x + pe
    x = lshard(x, "act_batch", "act_res_seq", "act_embed")

    def body(carry, bp, i):
        x, ck, cv = carry
        h = norm(x, bp["attn_norm"], cfg.norm_type, cfg.norm_eps)
        o, ck, cv = attend_step(cfg, bp["attn"], h, ck, cv, i, pos)
        x = x + lshard(o, "act_batch", "act_res_seq", "act_embed")
        x, _ = ffn_block_fwd(cfg, bp, x)
        return x, ck, cv

    carry = (x, cache["k"], cache["v"])
    if cfg.decode_unroll:
        for i in range(cfg.n_layers):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            carry = body(carry, bp, i)
    else:
        carry, _ = jax.lax.scan(
            lambda c, inp: (body(c, *inp), None), carry,
            (params["blocks"], jnp.arange(cfg.n_layers)))
    x, ks, vs = carry
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = logits_fwd(cfg, params, x)
    return logits, {"k": ks, "v": vs, "pos": pos + 1}
