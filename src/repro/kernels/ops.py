"""Jit'd model-layout wrappers around the Pallas kernels.

Nothing on the model path calls these yet: ``repro.models`` always takes
its pure-JAX chunked attention and scan, which implement the same
algorithms. The kernels are validated against those in interpret mode by
tests/test_kernels.py, and compiled for a described TPU v5e chip by
tests/test_tpu_compile.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd_scan import ssd_scan_kernel


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "causal",
                                   "block_q", "block_k", "interpret"))
def attention_bshd(q, k, v, *, n_heads, n_kv_heads, causal=True,
                   block_q=128, block_k=128, interpret=False):
    """Model layout: q (b, s, h, d); k/v (b, s, kvh, d) -> (b, s, h, d)."""
    b, s, h, d = q.shape
    skv = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * n_kv_heads, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * n_kv_heads, skv, d)
    of = flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                         block_k=block_k, n_heads=n_heads,
                         n_kv_heads=n_kv_heads, interpret=interpret)
    return of.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "block_k",
                                   "interpret"))
def decode_attention_bshd(q, k_cache, v_cache, kv_len, *, n_heads,
                          n_kv_heads, block_k=512, interpret=False):
    """q (b, 1, h, d); caches (b, S, kvh, d); kv_len (b,) -> (b, 1, h, d)."""
    b, _, h, d = q.shape
    S = k_cache.shape[1]
    qf = q[:, 0].reshape(b * h, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * n_kv_heads, S, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * n_kv_heads, S, d)
    of = flash_decode(qf, kf, vf, kv_len, block_k=block_k, n_heads=n_heads,
                      n_kv_heads=n_kv_heads, interpret=interpret)
    return of.reshape(b, 1, h, d)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bshn(x, dt, A, B, C, *, chunk=128, interpret=False):
    """Model layout: x (b, s, nh, p); dt (b, s, nh); A (nh,);
    B/C (b, s, g, n) -> (b, s, nh, p)."""
    b, s, nh, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = nh // g
    xf = x.transpose(0, 2, 1, 3).reshape(b * nh, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * nh, s)
    Bf = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(b * nh, s, n)
    Cf = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(b * nh, s, n)
    Af = jnp.tile(A, b)
    yf = ssd_scan_kernel(xf, dtf, Af, Bf, Cf, chunk=chunk,
                         interpret=interpret)
    return yf.reshape(b, nh, s, p).transpose(0, 2, 1, 3)
