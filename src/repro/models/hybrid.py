"""Hybrid backbones: Mamba-2 mixers beside attention, in two layouts.

Zamba2 (``cfg.hybrid.layer_types`` empty): Mamba-2 blocks plus one
parameter-shared attention(+MLP) block applied every ``attn_every`` SSM
blocks. For n_layers=38, attn_every=6: 6 groups of [6 mamba blocks ->
shared attn block] + 2 tail mamba blocks. The shared block's *weights* are
reused across applications; each application has its own KV-cache entries
at decode time.

Layer pattern (``cfg.hybrid.layer_types``, granite-4.0-h): layer l is the
mixer its type names, with weights of its own, then an MLP of its own:

    h = h + r * mixer(RMSNorm(h));  h = h + r * MLP(RMSNorm(h))

with r the residual multiplier. The pattern repeats with a period (granite:
four periods of [M x5, A, M x4]); the layer loop is a scan over periods with
the period's layers unrolled. Weights are three stacks, ``mamba``, ``attn``
and ``mlp``, each over its own layers in order; each layer indexes its own
weights out of its stack (a stack split by period would be relaid out, and
a period's slice copied, on every call). The decode cache holds both
kinds of state, each stacked over its own layers and carried through the
loop, where each layer writes only its own state and its rows' new position,
so a donated cache is updated in place:

    ssm_state (n_mamba, b, nh, hd, d_state) float32
    conv      (n_mamba, b, d_conv - 1, conv_dim)
    k, v      (n_attn, b, S, n_kv_heads * head_dim)   (as the transformer's)
    pos       (b,)

Both layouts keep their attention keys and values in that packed layout.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models import transformer as tfm
from repro.models.layers import (ParamDef, mlp_defs, mlp_fwd, norm,
                                 norm_defs, rmsnorm)
from repro.models.ssm import (mamba2_block_fwd, mamba2_decode_step,
                              mamba2_defs, mamba2_dims, mamba2_mixer,
                              mamba2_mixer_step)
from repro.sharding.partition import lshard

MAMBA, ATTENTION = "mamba", "attention"


def hybrid_layout(cfg: LMConfig) -> Tuple[int, int, int]:
    k = cfg.hybrid.attn_every
    n_groups = cfg.n_layers // k
    tail = cfg.n_layers - n_groups * k
    return n_groups, k, tail


def layer_period(cfg: LMConfig) -> Tuple[Tuple[str, ...], int]:
    """The shortest period of ``layer_types`` and how often it repeats."""
    types = tuple(cfg.hybrid.layer_types)
    if len(types) != cfg.n_layers or set(types) - {MAMBA, ATTENTION}:
        raise ValueError(f"{cfg.arch_id}: layer_types must name "
                         f"{cfg.n_layers} layers, each {MAMBA!r} or "
                         f"{ATTENTION!r}")
    n = len(types)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and types == types[:p] * (n // p))
    return types[:p], n // p


def _patterned(cfg: LMConfig) -> bool:
    return bool(cfg.hybrid.layer_types)


def hybrid_defs(cfg: LMConfig) -> Dict:
    if _patterned(cfg):
        return _pattern_defs(cfg)
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
    if _patterned(cfg):
        x, positions = tfm.embed_tokens(cfg, params, tokens, prefix_emb)
        x, _ = _pattern_trunk(cfg, params, x, positions, x.shape[1], remat)
        x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
        if return_hidden:
            return x, jnp.zeros((), jnp.float32)
        return tfm.logits_fwd(cfg, params, x), jnp.zeros((), jnp.float32)

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
    s = cfg.ssm
    di, nh, conv_dim = mamba2_dims(cfg)
    kv = cfg.n_kv_heads * cfg.resolved_head_dim
    dt = cfg.activation_dtype
    mk = (lambda sh, d: jax.ShapeDtypeStruct(sh, d)) if abstract \
        else (lambda sh, d: jnp.zeros(sh, d))
    if _patterned(cfg):
        n_m, n_a = _counts(cfg)
        return {
            "ssm_state": mk((n_m, batch, nh, s.head_dim, s.d_state),
                            jnp.float32),
            "conv": mk((n_m, batch, s.d_conv - 1, conv_dim), dt),
            "k": mk((n_a, batch, max_len, kv), dt),
            "v": mk((n_a, batch, max_len, kv), dt),
            "pos": mk((batch,), jnp.int32),
        }
    n_groups, k, tail = hybrid_layout(cfg)
    cache = {
        "ssm_state": mk((n_groups, k, batch, nh, s.head_dim, s.d_state),
                        jnp.float32),
        "conv": mk((n_groups, k, batch, s.d_conv - 1, conv_dim), dt),
        "ak": mk((n_groups, batch, max_len, kv), dt),
        "av": mk((n_groups, batch, max_len, kv), dt),
        "pos": mk((batch,), jnp.int32),
    }
    if tail:
        cache["tail_state"] = mk((tail, batch, nh, s.head_dim, s.d_state),
                                 jnp.float32)
        cache["tail_conv"] = mk((tail, batch, s.d_conv - 1, conv_dim), dt)
    return cache


def cache_axes(cfg: LMConfig):
    kv = ("layers", "cache_batch", "cache_seq", tfm._kv_axis(cfg))
    if _patterned(cfg):
        return {
            "ssm_state": ("layers", "cache_batch", "ssm_heads", None, None),
            "conv": ("layers", "cache_batch", None, "conv_dim"),
            "k": kv, "v": kv, "pos": ("cache_batch",),
        }
    n_groups, k, tail = hybrid_layout(cfg)
    ax = {
        "ssm_state": (None, None, "cache_batch", "ssm_heads", None, None),
        "conv": (None, None, "cache_batch", None, "conv_dim"),
        "ak": kv,
        "av": kv,
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
    if _patterned(cfg):
        x, cache = _pattern_trunk(cfg, params, x, positions, S)
        cache["pos"] = jnp.full((b,), s, jnp.int32)
        x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
        return tfm.logits_fwd(cfg, params, x[:, -1:, :]), cache

    def mamba_body(x, bp):
        out, st = mamba2_block_fwd(cfg, bp, x, return_state=True)
        return out, st

    def attn_apply(x):
        bp = params["shared_attn"]
        h = norm(x, bp["attn_norm"], cfg.norm_type, cfg.norm_eps)
        h = lshard(h, "act_batch", "act_seq", "act_embed")
        o, kk, vv = tfm.attend(cfg, bp["attn"], h, positions)
        x = x + lshard(o, "act_batch", "act_res_seq", "act_embed")
        x, _ = tfm.ffn_block_fwd(cfg, bp, x)
        return x, tfm.pack_kv(cfg, kk, S), tfm.pack_kv(cfg, vv, S)

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
    if _patterned(cfg):
        return _pattern_decode(cfg, params, cache, tokens)
    pos = cache["pos"]
    x = tfm.embed_rows(cfg, params, tokens)
    x = lshard(x, "act_batch", "act_res_seq", "act_embed")

    def mamba_body(x, inp):
        bp, st, cb = inp
        out, st, cb = mamba2_decode_step(cfg, bp, x, st, cb)
        return out, (st, cb)

    def attn_apply(x, k_cache, v_cache):
        # one application of the shared block: its own cache entries,
        # updated as a one-layer stack
        bp = params["shared_attn"]
        h = norm(x, bp["attn_norm"], cfg.norm_type, cfg.norm_eps)
        o, k_cache, v_cache = tfm.attend_step(cfg, bp["attn"], h,
                                              k_cache[None], v_cache[None],
                                              0, pos)
        x = x + o
        x, _ = tfm.ffn_block_fwd(cfg, bp, x)
        return x, k_cache[0], v_cache[0]

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


# ---------------------------------------------------------------------------
# the layer pattern (granite-4.0-h)
# ---------------------------------------------------------------------------

def _counts(cfg: LMConfig) -> Tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    kinds, reps = layer_period(cfg)
    return kinds.count(MAMBA) * reps, kinds.count(ATTENTION) * reps


def _pattern_defs(cfg: LMConfig) -> Dict:
    d = cfg.d_model
    n_m, n_a = _counts(cfg)
    nd = norm_defs(d, cfg.norm_type)
    out = {
        "embed": ParamDef((cfg.vocab, d), ("vocab", "embed"),
                          scale=d ** 0.5, dtype=cfg.dtype),
        "mamba": tfm.stacked(mamba2_defs(cfg), n_m),
        "attn": tfm.stacked({**tfm.attn_defs(cfg), "norm": nd}, n_a),
        "mlp": tfm.stacked({**mlp_defs(d, cfg.d_ff, cfg.gated_mlp,
                                       cfg.dtype), "norm": nd},
                           cfg.n_layers),
        "final_norm": norm_defs(d, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        out["unembed"] = ParamDef((d, cfg.vocab), ("embed", "vocab"),
                                  dtype=cfg.dtype)
    return out


def _layer(tree, i):
    """Layer ``i`` (traced) of a stack."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _mlp(cfg: LMConfig, p: Dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        h = norm(x, p["norm"], cfg.norm_type, cfg.norm_eps)
        h = lshard(h, "act_batch", "act_seq", "act_embed")
        y = mlp_fwd(p, h, cfg.act, cfg.gated_mlp)
        return x + cfg.residual_multiplier * y


def _pattern_trunk(cfg: LMConfig, params: Dict, x: jax.Array,
                   positions: jax.Array, max_len: int, remat: bool = False):
    """Every layer over whole prompts: the hidden states, and the decode
    cache (without ``pos``) with keys and values padded to ``max_len``."""
    kinds, reps = layer_period(cfg)
    n_m, n_a = _counts(cfg)
    m_per, a_per = kinds.count(MAMBA), kinds.count(ATTENTION)
    r = cfg.residual_multiplier

    def period(x, c):
        states, convs, ks, vs = [], [], [], []
        for j, kind in enumerate(kinds):
            if kind == MAMBA:
                with jax.named_scope("mamba2"):
                    bp = _layer(params["mamba"], c * m_per + len(states))
                    h = rmsnorm(x, bp["pre_norm"], cfg.norm_eps)
                    y, (st, conv) = mamba2_mixer(cfg, bp, h,
                                                 return_state=True)
                states.append(st)
                convs.append(conv)
            else:
                with jax.named_scope("attention"):
                    bp = _layer(params["attn"], c * a_per + len(ks))
                    h = norm(x, bp["norm"], cfg.norm_type, cfg.norm_eps)
                    h = lshard(h, "act_batch", "act_seq", "act_embed")
                    y, k, v = tfm.attend(cfg, bp, h, positions)
                ks.append(tfm.pack_kv(cfg, k, max_len))
                vs.append(tfm.pack_kv(cfg, v, max_len))
            x = x + r * lshard(y, "act_batch", "act_res_seq", "act_embed")
            x = _mlp(cfg, _layer(params["mlp"], c * len(kinds) + j), x)
        return x, tuple(jnp.stack(a) for a in (states, convs, ks, vs))

    if remat:
        period = jax.checkpoint(period, prevent_cse=False)
    x, (st, conv, k, v) = jax.lax.scan(period, x, jnp.arange(reps))
    flat = lambda a, n: a.reshape(n, *a.shape[2:])
    return x, {"ssm_state": flat(st, n_m), "conv": flat(conv, n_m),
               "k": flat(k, n_a), "v": flat(v, n_a)}


def _pattern_decode(cfg: LMConfig, params: Dict, cache: Dict,
                    tokens: jax.Array):
    """One position of every layer; the cache rides in the loop's carry."""
    kinds, reps = layer_period(cfg)
    m_per, a_per = kinds.count(MAMBA), kinds.count(ATTENTION)
    r = cfg.residual_multiplier
    pos = cache["pos"]
    x = lshard(tfm.embed_rows(cfg, params, tokens),
               "act_batch", "act_res_seq", "act_embed")

    def period(carry, c):
        x, st, conv, ck, cv = carry
        im = ia = 0
        for j, kind in enumerate(kinds):
            if kind == MAMBA:
                with jax.named_scope("mamba2"):
                    l = c * m_per + im
                    bp = _layer(params["mamba"], l)
                    h = rmsnorm(x, bp["pre_norm"], cfg.norm_eps)
                    y, s_l, c_l = mamba2_mixer_step(cfg, bp, h,
                                                    *_layer((st, conv), l))
                    st = jax.lax.dynamic_update_index_in_dim(st, s_l, l, 0)
                    conv = jax.lax.dynamic_update_index_in_dim(conv, c_l, l,
                                                               0)
                im += 1
            else:
                with jax.named_scope("attention"):
                    i = c * a_per + ia
                    bp = _layer(params["attn"], i)
                    h = norm(x, bp["norm"], cfg.norm_type, cfg.norm_eps)
                    y, ck, cv = tfm.attend_step(cfg, bp, h, ck, cv, i, pos)
                ia += 1
            x = x + r * lshard(y, "act_batch", "act_res_seq", "act_embed")
            x = _mlp(cfg, _layer(params["mlp"], c * len(kinds) + j), x)
        return (x, st, conv, ck, cv), None

    carry = (x, cache["ssm_state"], cache["conv"], cache["k"], cache["v"])
    carry, _ = jax.lax.scan(period, carry, jnp.arange(reps))
    x, st, conv, ks, vs = carry
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    return tfm.logits_fwd(cfg, params, x), {
        "ssm_state": st, "conv": conv, "k": ks, "v": vs, "pos": pos + 1}
