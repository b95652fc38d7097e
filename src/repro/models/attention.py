"""Chunked (flash-style) attention in pure JAX.

This is simultaneously:
  * the dry-run lowering path (algorithmically identical online-softmax
    chunking to the Pallas kernel, so HLO bytes are representative),
  * the numerical oracle for ``repro.kernels.flash_attention``,
  * the long-context path (memory is O(chunk), never O(seq²)).

Two causal schedules:
  * ``block_skip=False`` — rectangle schedule: every (q-chunk × kv-chunk) block
    is computed and masked. Simple; wastes ~2× FLOPs on causal masks.
  * ``block_skip=True`` — triangular schedule (beyond-paper §Perf
    optimization): only blocks with kv_chunk_start <= q_chunk_end are
    computed, recovering the ~2× for long sequences.

GQA layout convention: q is grouped as (b, s, g, m, hd) where g = n_kv_heads
and m = n_heads // n_kv_heads; k/v are (b, s, g, hd), or (b, s, g * hd) for
a decode cache (:func:`decode_attention`).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.partition import per_shard
from repro.sharding.rules import Packed

NEG_INF = -1e30


def group_query_heads(q: jax.Array, n_kv_heads: int) -> jax.Array:
    """(b, s, n_heads, hd) -> (b, s, g, m, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv_heads, h // n_kv_heads, hd)


def ungroup_heads(o: jax.Array) -> jax.Array:
    b, s, g, m, hd = o.shape
    return o.reshape(b, s, g * m, hd)


def _block(q_blk, k_blk, v_blk, m_prev, l_prev, acc, row0, col0,
           causal: bool, kv_len, scale: float):
    """One online-softmax block update.

    q_blk: (b, qc, g, m, hd)   k_blk/v_blk: (b, kc, g, hd)
    m_prev/l_prev: (b, g, m, qc)  acc: (b, qc, g, m, hd) fp32
    """
    qc, kc = q_blk.shape[1], k_blk.shape[1]
    s = jnp.einsum("bqgmh,bkgh->bgmqk", q_blk, k_blk,
                   preferred_element_type=jnp.float32) * scale
    rows = row0 + jnp.arange(qc)
    cols = col0 + jnp.arange(kc)
    mask = None
    if causal:
        mask = rows[:, None] >= cols[None, :]
    if kv_len is not None:
        lm = cols[None, :] < jnp.reshape(kv_len, (-1, 1))        # (b, kc)
        lm = lm[:, None, None, None, :]                          # (b,1,1,1,kc)
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    pv = jnp.einsum("bgmqk,bkgh->bqgmh", p.astype(v_blk.dtype), v_blk,
                    preferred_element_type=jnp.float32)
    acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
    return m_new, l_new, acc


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      kv_len: Optional[jax.Array] = None,
                      q_offset: int = 0,
                      block_skip: bool = False,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention over (q, kv) chunks.

    q: (b, sq, g, m, hd); k, v: (b, skv, g, hd). Returns (b, sq, g, m, hd).
    ``kv_len`` (scalar or (b,)) masks cache positions >= kv_len.
    ``q_offset``: absolute position of q[0] (for decode-with-history).
    ``scale`` multiplies the scores (default 1/sqrt(hd)).
    """
    b, sq, g, m, hd = q.shape
    skv = k.shape[1]
    sq0, skv0 = sq, skv
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    qpad, kpad = (-sq) % qc, (-skv) % kc
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
        sq += qpad
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        skv += kpad
        if kv_len is None:
            kv_len = jnp.full((b,), skv0, jnp.int32)
    nq, nk = sq // qc, skv // kc
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qs = q.reshape(b, nq, qc, g, m, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(b, nk, kc, g, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kc, g, hd).transpose(1, 0, 2, 3, 4)

    if kv_len is not None:
        kv_len = jnp.asarray(kv_len).reshape(-1)

    if not block_skip:
        def outer(_, inp):
            qi, q_blk = inp
            init = (jnp.full((b, g, m, qc), NEG_INF, jnp.float32),
                    jnp.zeros((b, g, m, qc), jnp.float32),
                    jnp.zeros((b, qc, g, m, hd), jnp.float32))

            @jax.checkpoint
            def inner(carry, kinp):
                # checkpointed: the (qc×kc) probability block is recomputed in
                # the backward pass instead of being stored per step
                kj, k_blk, v_blk = kinp
                mx, l, acc = _block(
                    q_blk, k_blk, v_blk, *carry,
                    row0=q_offset + qi * qc, col0=kj * kc,
                    causal=causal, kv_len=kv_len, scale=scale)
                return (mx, l, acc), None

            (mx, l, acc), _ = jax.lax.scan(
                inner, init, (jnp.arange(nk), ks, vs))
            out = acc / jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None]
            return None, out.astype(q.dtype)

        _, outs = jax.lax.scan(outer, None, (jnp.arange(nq), qs))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, g, m, hd)
        return out[:, :sq0]

    # ---- triangular block schedule (causal only) ------------------------
    if not causal:
        raise ValueError("block_skip requires causal attention")
    pairs = [(qi, kj) for qi in range(nq) for kj in range(nk)
             if kj * kc <= q_offset + qi * qc + qc - 1]
    qi_arr = jnp.asarray(np.array([p[0] for p in pairs], np.int32))
    kj_arr = jnp.asarray(np.array([p[1] for p in pairs], np.int32))

    init = (jnp.full((nq, b, g, m, qc), NEG_INF, jnp.float32),
            jnp.zeros((nq, b, g, m, qc), jnp.float32),
            jnp.zeros((nq, b, qc, g, m, hd), jnp.float32))

    @jax.checkpoint
    def body(carry, inp):
        m_all, l_all, acc_all = carry
        qi, kj = inp
        q_blk = jax.lax.dynamic_index_in_dim(qs, qi, 0, keepdims=False)
        k_blk = jax.lax.dynamic_index_in_dim(ks, kj, 0, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vs, kj, 0, keepdims=False)
        mx = jax.lax.dynamic_index_in_dim(m_all, qi, 0, keepdims=False)
        l = jax.lax.dynamic_index_in_dim(l_all, qi, 0, keepdims=False)
        acc = jax.lax.dynamic_index_in_dim(acc_all, qi, 0, keepdims=False)
        mx, l, acc = _block(q_blk, k_blk, v_blk, mx, l, acc,
                            row0=q_offset + qi * qc, col0=kj * kc,
                            causal=True, kv_len=kv_len, scale=scale)
        m_all = jax.lax.dynamic_update_index_in_dim(m_all, mx, qi, 0)
        l_all = jax.lax.dynamic_update_index_in_dim(l_all, l, qi, 0)
        acc_all = jax.lax.dynamic_update_index_in_dim(acc_all, acc, qi, 0)
        return (m_all, l_all, acc_all), None

    (m_all, l_all, acc_all), _ = jax.lax.scan(body, init, (qi_arr, kj_arr))
    out = acc_all / jnp.maximum(l_all, 1e-37).transpose(0, 1, 4, 2, 3)[..., None]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, g, m, hd) \
        .astype(q.dtype)
    return out[:, :sq0]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-position attention against a (padded) KV cache whose kv heads
    share one minor axis.

    q: (b, 1, g, m, hd); caches: (b, S, g * hd); kv_len: scalar or (b,);
    ``scale`` multiplies the scores (default 1/sqrt(hd)).
    Both products contract the packed axis or the sequence, each as one
    matrix product per row, so the caches are read in the layout they are
    stored in: q is spread block-diagonally over the g groups, and each
    head keeps its own group's slice of the output. A head_dim below the
    TPU's 128 lanes never becomes a minor axis of a cache-sized operand.

    Under a mesh each shard attends with its own rows and kv heads
    (:func:`~repro.sharding.partition.per_shard`), so no product sums
    across shards; the packed axis shards whole heads only. A cache
    sharded along the sequence is left to GSPMD, which merges the softmax
    partials (flash-decode style).
    """
    b, _, g = q.shape[:3]
    kv = Packed("cache_kv_heads", g)
    q_axes = ("cache_batch", None, kv, None, None)
    cache_axes = ("cache_batch", None, kv)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return per_shard(partial(_decode_attention, scale=scale),
                     (q_axes, cache_axes, cache_axes, ("cache_batch",)),
                     q_axes)(q, k_cache, v_cache, kv_len)


def _decode_attention(q, k_cache, v_cache, kv_len, scale):
    b, _, g, m, hd = q.shape
    S = k_cache.shape[1]
    own = jnp.eye(g, dtype=bool)
    q_bd = jnp.where(own[:, None, :, None], q[:, 0, :, :, None], 0)
    q_bd = q_bd.reshape(b, g * m, g * hd)
    s = jnp.einsum("bjc,bkc->bjk", q_bd, k_cache,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, :] < kv_len[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bjk,bkc->bjc", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    # each head's own group: a select and a sum over zeros, exact
    o = jnp.where(own[:, None, :, None], o.reshape(b, g, m, g, hd), 0)
    return o.sum(axis=3)[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# flash-style custom VJP (train-cell §Perf lever)
#
# The autodiff of the chunked forward either stores per-block probabilities
# (O(s²/chunk) residuals) or, checkpointed, recomputes whole blocks through
# HBM. The flash backward saves only (o, L=m+log l) per row and rebuilds each
# probability block in VMEM-sized tiles:  p = exp(qkᵀ·scale − L);
# dv += pᵀ do;  ds = p∘(do vᵀ − Δ);  dq += ds k;  dk += dsᵀ q,  Δ = Σ(do∘o).
# ---------------------------------------------------------------------------

from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_jax(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, q_chunk: int = 512,
                        kv_chunk: int = 1024) -> jax.Array:
    """chunked_attention with a flash backward. Same layout/semantics as
    :func:`chunked_attention` (no kv_len/q_offset: training path)."""
    out, _ = _flash_fwd_stats(q, k, v, causal, q_chunk, kv_chunk)
    return out


def _flash_fwd_stats(q, k, v, causal, q_chunk, kv_chunk):
    b, sq, g, m, hd = q.shape
    skv = k.shape[1]
    qc, kc = min(q_chunk, sq), min(kv_chunk, skv)
    assert sq % qc == 0 and skv % kc == 0, (sq, qc, skv, kc)
    nq, nk = sq // qc, skv // kc
    scale = 1.0 / math.sqrt(hd)
    qs = q.reshape(b, nq, qc, g, m, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(b, nk, kc, g, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kc, g, hd).transpose(1, 0, 2, 3, 4)

    def outer(_, inp):
        qi, q_blk = inp
        init = (jnp.full((b, g, m, qc), NEG_INF, jnp.float32),
                jnp.zeros((b, g, m, qc), jnp.float32),
                jnp.zeros((b, qc, g, m, hd), jnp.float32))

        @jax.checkpoint
        def inner(carry, kinp):
            kj, k_blk, v_blk = kinp
            return _block(q_blk, k_blk, v_blk, *carry, row0=qi * qc,
                          col0=kj * kc, causal=causal, kv_len=None,
                          scale=scale), None

        (mx, l, acc), _ = jax.lax.scan(inner, init,
                                       (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None]
        L = mx + jnp.log(jnp.maximum(l, 1e-37))          # (b, g, m, qc)
        return None, (out.astype(q.dtype), L)

    _, (outs, Ls) = jax.lax.scan(outer, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, g, m, hd)
    return out, Ls                                        # Ls: (nq, b, g, m, qc)


def _flash_fwd_rule(q, k, v, causal, q_chunk, kv_chunk):
    out, Ls = _flash_fwd_stats(q, k, v, causal, q_chunk, kv_chunk)
    return out, (q, k, v, out, Ls)


def _flash_bwd_rule(causal, q_chunk, kv_chunk, res, dout):
    q, k, v, out, Ls = res
    b, sq, g, m, hd = q.shape
    skv = k.shape[1]
    qc, kc = min(q_chunk, sq), min(kv_chunk, skv)
    nq, nk = sq // qc, skv // kc
    scale = 1.0 / math.sqrt(hd)
    qs = q.reshape(b, nq, qc, g, m, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(b, nk, kc, g, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kc, g, hd).transpose(1, 0, 2, 3, 4)
    dos = dout.reshape(b, nq, qc, g, m, hd).transpose(1, 0, 2, 3, 4, 5)
    os_ = out.reshape(b, nq, qc, g, m, hd).transpose(1, 0, 2, 3, 4, 5)
    # Δ[i] = Σ_h do∘o  per row: (nq, b, g, m, qc)
    delta = jnp.einsum("nbqgmh,nbqgmh->nbgmq", dos.astype(jnp.float32),
                       os_.astype(jnp.float32))

    def outer(carry, inp):
        dk_acc, dv_acc = carry                 # (nk, b, kc, g, hd) fp32
        qi, q_blk, do_blk, L_blk, d_blk = inp

        @jax.checkpoint
        def inner(dq, kinp):
            kj, k_blk, v_blk = kinp
            s = jnp.einsum("bqgmh,bkgh->bgmqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                rows = qi * qc + jnp.arange(qc)
                cols = kj * kc + jnp.arange(kc)
                s = jnp.where(rows[:, None] >= cols[None, :], s, NEG_INF)
            p = jnp.exp(s - L_blk[..., None])                 # (b,g,m,qc,kc)
            dv = jnp.einsum("bgmqk,bqgmh->bkgh", p,
                            do_blk.astype(jnp.float32))
            dp = jnp.einsum("bqgmh,bkgh->bgmqk",
                            do_blk.astype(jnp.float32), v_blk)
            ds = p * (dp - d_blk[..., None]) * scale
            dq = dq + jnp.einsum("bgmqk,bkgh->bqgmh", ds, k_blk)
            dk = jnp.einsum("bgmqk,bqgmh->bkgh", ds, q_blk)
            return dq, (dk, dv)

        dq0 = jnp.zeros((b, qc, g, m, hd), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(inner, dq0,
                                      (jnp.arange(nk), ks, vs))
        return (dk_acc + dks, dv_acc + dvs), dq

    zero_kv = jnp.zeros((nk, b, kc, g, hd), jnp.float32)
    (dk_all, dv_all), dqs = jax.lax.scan(
        outer, (zero_kv, zero_kv), (jnp.arange(nq), qs, dos, Ls, delta))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, g, m, hd)
    dk = dk_all.transpose(1, 0, 2, 3, 4).reshape(b, skv, g, hd)
    dv = dv_all.transpose(1, 0, 2, 3, 4).reshape(b, skv, g, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_jax.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def reference_attention(q, k, v, *, causal=True, kv_len=None, q_offset=0):
    """O(s²)-memory oracle used by tests (never by the system itself)."""
    b, sq, g, m, hd = q.shape
    skv = k.shape[1]
    s = jnp.einsum("bqgmh,bkgh->bgmqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    rows = q_offset + jnp.arange(sq)
    cols = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = rows[:, None] >= cols[None, :]
    if kv_len is not None:
        lm = cols[None, :] < jnp.reshape(jnp.asarray(kv_len), (-1, 1))
        s = jnp.where(lm[:, None, None, None, :], s, NEG_INF)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgmqk,bkgh->bqgmh", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
