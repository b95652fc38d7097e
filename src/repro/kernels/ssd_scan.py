"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Layout (heads pre-expanded from B/C groups by the wrapper):
  x  (BH, NC, Q, P)   head streams, chunked
  dt (BH, NC, 1, Q)   softplus'd step sizes (a unit sublane dim, so the
                      block's last two dims (1, Q) equal the array's)
  B  (BH, NC, Q, N)   input projections
  C  (BH, NC, Q, N)   output projections
  A  (BH,)            per-head negative decay rate

Grid = (BH, NC) with the chunk dimension innermost-sequential; the running
inter-chunk state S (N×P) lives in VMEM scratch, reset at chunk 0. Each grid
step does the intra-chunk quadratic part (Q×Q decay-masked scores on the MXU)
plus the contribution of the incoming state — identical math to the pure-JAX
``repro.models.ssm.ssd_scan`` oracle.

VMEM at Q=128, N=64, P=64 fp32: x/B/C tiles ≈ 3·128·64·4 ≈ 96 KB, scores
128·128·4 = 64 KB, state 64·64·4 = 16 KB — comfortably inside 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dot(a, b, ca: int, cb: int):
    """f32 matmul contracting a's dim ``ca`` with b's dim ``cb``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, *, Q: int):
    i = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (1, Q)
    B = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)           # (Q, N)
    A = a_ref[i]                                  # scalar (negative)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = rows >= cols
    dA = dt * A                                   # (1, Q)
    # Mosaic has no cumsum: the prefix sums are matmuls against the
    # causal mask, once as a row and once as a column
    tril = jnp.where(causal, 1.0, 0.0)
    cum = _dot(dA, tril, 1, 1)                    # (1, Q)
    cum_col = _dot(tril, dA, 1, 1)                # (Q, 1)
    # intra-chunk: y[q] += sum_{j<=q} exp(cum_q - cum_j)·dt_j·(C_q·B_j)·x_j
    scores = _dot(C, B, 1, 1)                     # (Q, Q)
    L = jnp.where(causal, cum_col - cum, NEG_INF)
    wgt = jnp.exp(L) * scores * dt
    y = _dot(wgt, x, 1, 0)
    # inter-chunk: y[q] += exp(cum_q) · C_q · S_in
    y = y + jnp.exp(cum_col) * _dot(C, s_ref[...], 1, 0)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: S_out = exp(cum_last)·S_in + Σ_j exp(cum_last-cum_j)·dt_j·B_j⊗x_j
    cum_last = cum[:, Q - 1:]                     # (1, 1)
    decay_end = jnp.exp(cum_last - cum) * dt      # (1, Q)
    B_w = _dot(jnp.where(rows == cols, decay_end, 0.0), B, 1, 0)
    # the chunk's total decay as an (N, 1) column: Mosaic cannot broadcast
    # a (1, 1) across both sublanes and lanes
    total = _dot(jnp.ones((s_ref.shape[0], Q), jnp.float32), dA, 1, 1)
    s_ref[...] = s_ref[...] * jnp.exp(total) + _dot(B_w, x, 0, 0)


def ssd_scan_kernel(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                    C: jax.Array, *, chunk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """x: (BH, S, P); dt: (BH, S); A: (BH,); B, C: (BH, S, N) -> (BH, S, P)."""
    BH, S, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    NC = S // Q
    xs = x.reshape(BH, NC, Q, P)
    dts = dt.reshape(BH, NC, 1, Q)
    Bs = B.reshape(BH, NC, Q, N)
    Cs = C.reshape(BH, NC, Q, N)
    kernel = functools.partial(_kernel, Q=Q)
    out = pl.pallas_call(
        kernel,
        grid=(BH, NC),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # A
            pl.BlockSpec((1, 1, Q, P), lambda i, c: (i, c, 0, 0)),  # x
            pl.BlockSpec((1, 1, 1, Q), lambda i, c: (i, c, 0, 0)),  # dt
            pl.BlockSpec((1, 1, Q, N), lambda i, c: (i, c, 0, 0)),  # B
            pl.BlockSpec((1, 1, Q, N), lambda i, c: (i, c, 0, 0)),  # C
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda i, c: (i, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, NC, Q, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), xs, dts, Bs, Cs)
    return out.reshape(BH, S, P)
