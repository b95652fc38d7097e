"""State-space / recurrent blocks: Mamba-2 (SSD) and xLSTM (mLSTM + sLSTM).

Mamba-2 uses the chunked SSD algorithm (intra-chunk quadratic + inter-chunk
state scan) — the same blocking the Pallas ``ssd_scan`` kernel implements, so
the pure-JAX path is both oracle and dry-run lowering path.

mLSTM uses the stabilized chunkwise-parallel form (exponential gating with a
running max-stabilizer carried across chunks). sLSTM is inherently sequential
(recurrent gate preactivations) and is implemented as a time scan.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models.layers import ParamDef, act_fn, mlp_defs, mlp_fwd, norm, \
    norm_defs, rmsnorm
from repro.sharding.partition import lshard

NEG_INF = -1e30


# ===========================================================================
# Mamba-2 (SSD)
# ===========================================================================

def mamba2_dims(cfg: LMConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, nheads, conv_dim


def mamba2_defs(cfg: LMConfig) -> Dict[str, ParamDef]:
    s = cfg.ssm
    d = cfg.d_model
    di, nh, conv_dim = mamba2_dims(cfg)
    proj_out = 2 * di + 2 * s.n_groups * s.d_state + nh
    dt = cfg.dtype
    return {
        "in_proj": ParamDef((d, proj_out), ("embed", "ssm_inner"), dtype=dt),
        "conv_w": ParamDef((s.d_conv, conv_dim), (None, "conv_dim"),
                           init="normal", dtype=dt),
        "conv_b": ParamDef((conv_dim,), ("conv_dim",), init="zeros", dtype=dt),
        "A_log": ParamDef((nh,), ("ssm_heads",), init="zeros", dtype="float32"),
        "dt_bias": ParamDef((nh,), ("ssm_heads",), init="zeros",
                            dtype="float32"),
        "D": ParamDef((nh,), ("ssm_heads",), init="ones", dtype="float32"),
        "norm": ParamDef((di,), ("ssm_inner",), init="ones", dtype="float32"),
        "out_proj": ParamDef((di, d), ("ssm_inner", "embed"), dtype=dt),
        "pre_norm": norm_defs(d, cfg.norm_type)["scale"],
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (b, s, ch), w: (k, ch)."""
    k, ch = w.shape
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :], window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=ch)
    return out + b


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int,
             init_state: Optional[jax.Array] = None):
    """Chunked state-space-dual scan, one chunk after another.

    x: (b, s, nh, hd); dt: (b, s, nh); A: (nh,) (negative);
    B, C: (b, s, g, n) with nh % g == 0.
    Returns (y (b, s, nh, hd), final_state (b, nh, hd, n)).

    Within a chunk, with cum the running sum of dt·A from the chunk's
    start: y_i = sum_{j<=i} exp(cum_i - cum_j) dt_j (C_i·B_j) x_j (the
    quadratic form, C·B once per group) plus exp(cum_i) C_i·S, where S is
    the state entering the chunk. The loop carries S in float32; one
    chunk's (Q, Q) tiles are all that is held at a time.
    """
    b, s, nh, hd = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = nh // g
    Q = min(chunk, s)
    s0 = s
    pad = (-s) % Q
    if pad:
        # dt=0 on padded steps => decay exp(0·A)=1 and zero state writes, so
        # the final state is exactly the state at s0 (padding is inert).
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s += pad
    nc = s // Q
    # chunk-major, heads split by group: (nc, b, Q, g, rep, ...)
    chunks = lambda a: jnp.moveaxis(a.reshape(b, nc, Q, *a.shape[2:]), 1, 0)
    xs = chunks(x.reshape(b, s, g, rep, hd))
    dts = chunks(dt.reshape(b, s, g, rep))
    Bs, Cs = chunks(B), chunks(C)
    Ah = A.reshape(g, rep)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def body(S, inp):                    # S: (b, g, rep, hd, n) float32
        xq, dtq, Bq, Cq = inp
        cum = jnp.cumsum(dtq * Ah, axis=1)                    # (b,Q,g,rep)
        cum_t = jnp.moveaxis(cum, 1, -1)                      # (b,g,rep,Q)
        seg = cum_t[..., :, None] - cum_t[..., None, :]       # [i, j]
        decay = jnp.exp(jnp.where(causal, seg, NEG_INF))
        scores = jnp.einsum("bign,bjgn->bgij", Cq, Bq,
                            preferred_element_type=jnp.float32)
        wgt = decay * scores[:, :, None] \
            * jnp.moveaxis(dtq, 1, -1)[..., None, :]          # (b,g,rep,i,j)
        y = jnp.einsum("bgrij,bjgrp->bigrp", wgt.astype(x.dtype), xq,
                       preferred_element_type=jnp.float32)
        y = y + jnp.einsum("bign,bgrpn->bigrp", Cq, S.astype(x.dtype),
                           preferred_element_type=jnp.float32) \
            * jnp.exp(cum)[..., None]
        # the state leaving the chunk
        w_end = jnp.exp(cum[:, -1:] - cum) * dtq                # (b,Q,g,rep)
        S = S * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "bjgrp,bjgn->bgrpn", (xq * w_end[..., None]).astype(x.dtype),
            Bq, preferred_element_type=jnp.float32)
        return S, y

    S0 = jnp.zeros((b, g, rep, hd, n), jnp.float32) if init_state is None \
        else init_state.astype(jnp.float32).reshape(b, g, rep, hd, n)
    Sf, ys = jax.lax.scan(body, S0, (xs, dts, Bs, Cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, nh, hd)[:, :s0]
    return y.astype(x.dtype), Sf.reshape(b, nh, hd, n)


def mamba2_split(cfg: LMConfig, zxbcdt: jax.Array):
    s = cfg.ssm
    di, nh, _ = mamba2_dims(cfg)
    gn = s.n_groups * s.d_state
    z, xin, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + gn, 2 * di + 2 * gn], axis=-1)
    return z, xin, B, C, dt


def mamba2_mixer(cfg: LMConfig, p: Dict, h: jax.Array,
                 init_state: Optional[jax.Array] = None,
                 return_state: bool = False):
    """The Mamba-2 mixer over a normed sequence, h: (b, s, d) -> (b, s, d),
    without the block's residual. With ``return_state``, also the state
    and the conv window that decoding continues from."""
    s_cfg = cfg.ssm
    b, s, d = h.shape
    di, nh, conv_dim = mamba2_dims(cfg)
    zxbcdt = jnp.einsum("bsd,dk->bsk", h, p["in_proj"])
    z, xin, B, C, dtr = mamba2_split(cfg, zxbcdt)
    xBC_raw = jnp.concatenate([xin, B, C], axis=-1)
    xBC = jax.nn.silu(_causal_conv(xBC_raw, p["conv_w"], p["conv_b"]))
    xin, B, C = jnp.split(xBC, [di, di + s_cfg.n_groups * s_cfg.d_state],
                          axis=-1)
    xh = xin.reshape(b, s, nh, s_cfg.head_dim)
    xh = lshard(xh, "act_batch", "act_seq", "act_ssm_inner", None)
    Bg = B.reshape(b, s, s_cfg.n_groups, s_cfg.d_state)
    Cg = C.reshape(b, s, s_cfg.n_groups, s_cfg.d_state)
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, Sf = ssd_scan(xh, dt, A, Bg, Cg, s_cfg.chunk_size, init_state)
    y = y + (p["D"][:, None] * xh.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(b, s, di)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"])
    if return_state:
        if s < s_cfg.d_conv - 1:
            raise ValueError(f"a prompt of {s} positions is shorter than "
                             f"the conv window ({s_cfg.d_conv - 1})")
        return out, (Sf, xBC_raw[:, s - (s_cfg.d_conv - 1):, :])
    return out


def mamba2_block_fwd(cfg: LMConfig, p: Dict, x: jax.Array,
                     init_state: Optional[jax.Array] = None,
                     return_state: bool = False):
    """Full-sequence Mamba-2 block: x + mixer(RMSNorm(x)). x: (b, s, d)."""
    h = rmsnorm(x, p["pre_norm"], cfg.norm_eps)
    y = mamba2_mixer(cfg, p, h, init_state, return_state)
    state = None
    if return_state:
        y, state = y
    out = lshard(x + y, "act_batch", "act_res_seq", "act_embed")
    return (out, state) if return_state else out


def mamba2_mixer_step(cfg: LMConfig, p: Dict, h: jax.Array,
                      state: jax.Array, conv_buf: jax.Array):
    """One position of the Mamba-2 mixer. h: (b, 1, d), normed; state:
    (b, nh, hd, n) float32; conv_buf: (b, d_conv-1, conv_dim). Returns
    (out (b, 1, d), state', conv_buf'), without the block's residual."""
    s_cfg = cfg.ssm
    b = h.shape[0]
    di, nh, conv_dim = mamba2_dims(cfg)
    zxbcdt = jnp.einsum("bsd,dk->bsk", h, p["in_proj"])
    z, xin, B, C, dtr = mamba2_split(cfg, zxbcdt)
    xBC_new = jnp.concatenate([xin, B, C], axis=-1)           # (b, 1, conv_dim)
    win = jnp.concatenate([conv_buf, xBC_new], axis=1)        # (b, d_conv, ch)
    conv_out = jnp.einsum("bkc,kc->bc", win, p["conv_w"]) + p["conv_b"]
    xBC = jax.nn.silu(conv_out)[:, None, :]
    xin, B, C = jnp.split(xBC, [di, di + s_cfg.n_groups * s_cfg.d_state],
                          axis=-1)
    xh = xin.reshape(b, nh, s_cfg.head_dim)
    rep = nh // s_cfg.n_groups
    Bh = jnp.repeat(B.reshape(b, s_cfg.n_groups, s_cfg.d_state), rep, axis=1)
    Ch = jnp.repeat(C.reshape(b, s_cfg.n_groups, s_cfg.d_state), rep, axis=1)
    dt = jax.nn.softplus(dtr[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                      # (b, nh)
    state = state * dA[..., None, None] + jnp.einsum(
        "bhp,bhn,bh->bhpn", xh.astype(jnp.float32), Bh.astype(jnp.float32), dt)
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch.astype(jnp.float32))
    y = y + p["D"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(b, 1, di).astype(h.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return jnp.einsum("bsk,kd->bsd", y, p["out_proj"]), state, win[:, 1:, :]


def mamba2_decode_step(cfg: LMConfig, p: Dict, x: jax.Array,
                       state: jax.Array, conv_buf: jax.Array):
    """One-token Mamba-2 block: x + mixer step. x: (b, 1, d). Returns
    (out, state', conv_buf')."""
    h = rmsnorm(x, p["pre_norm"], cfg.norm_eps)
    y, state, conv_buf = mamba2_mixer_step(cfg, p, h, state, conv_buf)
    return x + y, state, conv_buf


# ===========================================================================
# xLSTM — mLSTM (chunkwise parallel) + sLSTM (time scan)
# ===========================================================================

def xlstm_dims(cfg: LMConfig):
    x = cfg.xlstm
    di = x.proj_factor_m * cfg.d_model
    nh = cfg.n_heads
    return di, nh, di // nh


def mlstm_defs(cfg: LMConfig) -> Dict[str, ParamDef]:
    d = cfg.d_model
    di, nh, dh = xlstm_dims(cfg)
    dt = cfg.dtype
    return {
        "pre_norm": norm_defs(d, "rmsnorm"),
        "up": ParamDef((d, 2 * di), ("embed", "ssm_inner"), dtype=dt),
        "wq": ParamDef((di, di), ("ssm_inner", None), dtype=dt),
        "wk": ParamDef((di, di), ("ssm_inner", None), dtype=dt),
        "wv": ParamDef((di, di), ("ssm_inner", None), dtype=dt),
        "wif": ParamDef((di, 2 * nh), ("ssm_inner", None), dtype="float32"),
        "norm": ParamDef((di,), ("ssm_inner",), init="ones", dtype="float32"),
        "down": ParamDef((di, d), ("ssm_inner", "embed"), dtype=dt),
    }


def _headwise_rmsnorm(y: jax.Array, w: jax.Array, nh: int, eps: float):
    b, s, di = y.shape
    yh = y.reshape(b, s, nh, di // nh)
    yf = yh.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    yn = (yf * jax.lax.rsqrt(var + eps)).reshape(b, s, di)
    return (yn * w.astype(jnp.float32)).astype(y.dtype)


def mlstm_chunkwise(q, k, v, li, lf, chunk: int, init=None):
    """Stabilized chunkwise mLSTM.

    q/k/v: (b, s, nh, dh); li/lf: (b, s, nh) log input/forget gates.
    Returns (h (b,s,nh,dh), (C, n, m) final states).
    """
    b, s, nh, dh = q.shape
    L = min(chunk, s)
    s0 = s
    pad = (-s) % L
    if pad:
        # li=-inf (no write), lf=0 (no decay) on padded steps keeps the final
        # (C, n, m) exactly equal to the state at s0.
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        li = jnp.pad(li, ((0, 0), (0, pad), (0, 0)),
                     constant_values=NEG_INF)
        lf = jnp.pad(lf, ((0, 0), (0, pad), (0, 0)))
        s += pad
    nc = s // L
    k = k / math.sqrt(dh)
    qc = q.reshape(b, nc, L, nh, dh)
    kc = k.reshape(b, nc, L, nh, dh)
    vc = v.reshape(b, nc, L, nh, dh)
    lic = li.reshape(b, nc, L, nh)
    lfc = lf.reshape(b, nc, L, nh)
    F = jnp.cumsum(lfc, axis=2)                                # inclusive
    Ftot = F[:, :, -1, :]                                      # (b, nc, nh)
    gvec = Ftot[:, :, None, :] - F + lic                       # (b, nc, L, nh)
    # intra-chunk decay D_ij = F_i - lf_i? -> F_i - F_j + li_j, j <= i
    Dm = F[:, :, :, None, :] - F[:, :, None, :, :] + lic[:, :, None, :, :]
    tri = jnp.tril(jnp.ones((L, L), bool))
    Dm = jnp.where(tri[None, None, :, :, None], Dm, NEG_INF)
    scores = jnp.einsum("bclhd,bcmhd->bclmh", qc, kc,
                        preferred_element_type=jnp.float32)

    if init is None:
        C0 = jnp.zeros((b, nh, dh, dh), jnp.float32)
        n0 = jnp.zeros((b, nh, dh), jnp.float32)
        m0 = jnp.full((b, nh), -jnp.inf, jnp.float32)
    else:
        C0, n0, m0 = init

    def chunk_step(carry, inp):
        C, n, m = carry
        q_c, k_c, v_c, F_c, Ftot_c, g_c, D_c, s_c = inp
        a = F_c + m[:, None, :]                                # (b, L, nh)
        m_intra = jnp.max(D_c, axis=2)                         # (b, L, nh)
        m_i = jnp.maximum(m_intra, a)
        w_inter = jnp.exp(a - m_i)                             # (b, L, nh)
        wgt = jnp.exp(D_c - m_i[:, :, None, :])                # (b, L, L, nh)
        qf = q_c.astype(jnp.float32)
        num = w_inter[..., None] * jnp.einsum("blhd,bhde->blhe", qf, C) \
            + jnp.einsum("blmh,bmhe->blhe", wgt * s_c,
                         v_c.astype(jnp.float32))
        den = w_inter * jnp.einsum("blhd,bhd->blh", qf, n) \
            + jnp.sum(wgt * s_c, axis=2)                       # (b, L, nh)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))[..., None]
        # state update to end of chunk
        m_new = jnp.maximum(m + Ftot_c, jnp.max(g_c, axis=1))  # (b, nh)
        sc_old = jnp.exp(m + Ftot_c - m_new)
        wg = jnp.exp(g_c - m_new[:, None, :])                  # (b, L, nh)
        C = C * sc_old[..., None, None] + jnp.einsum(
            "blhd,blhe->bhde", (k_c * wg[..., None]).astype(jnp.float32),
            v_c.astype(jnp.float32))
        n = n * sc_old[..., None] + jnp.einsum(
            "blhd->bhd", (k_c * wg[..., None]).astype(jnp.float32))
        return (C, n, m_new), h

    xs = (qc.transpose(1, 0, 2, 3, 4), kc.transpose(1, 0, 2, 3, 4),
          vc.transpose(1, 0, 2, 3, 4), F.transpose(1, 0, 2, 3),
          Ftot.transpose(1, 0, 2), gvec.transpose(1, 0, 2, 3),
          Dm.transpose(1, 0, 2, 3, 4), scores.transpose(1, 0, 2, 3, 4))
    (C, n, m), hs = jax.lax.scan(chunk_step, (C0, n0, m0), xs)
    h = hs.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, dh)[:, :s0]
    return h.astype(q.dtype), (C, n, m)


def mlstm_block_fwd(cfg: LMConfig, p: Dict, x: jax.Array, init=None,
                    return_state: bool = False):
    di, nh, dh = xlstm_dims(cfg)
    b, s, d = x.shape
    h = norm(x, p["pre_norm"], "rmsnorm", cfg.norm_eps)
    up = jnp.einsum("bsd,dk->bsk", h, p["up"])
    xi, z = jnp.split(up, 2, axis=-1)
    xi = lshard(xi, "act_batch", "act_seq", "act_ssm_inner")
    q = jnp.einsum("bsk,kj->bsj", xi, p["wq"]).reshape(b, s, nh, dh)
    k = jnp.einsum("bsk,kj->bsj", xi, p["wk"]).reshape(b, s, nh, dh)
    v = jnp.einsum("bsk,kj->bsj", xi, p["wv"]).reshape(b, s, nh, dh)
    gates = jnp.einsum("bsk,kj->bsj", xi.astype(jnp.float32), p["wif"])
    li, lfr = jnp.split(gates, 2, axis=-1)                     # (b, s, nh)
    lf = jax.nn.log_sigmoid(lfr + 3.0)                         # forget bias +3
    y, state = mlstm_chunkwise(q, k, v, li, lf, cfg.xlstm.chunk_size, init)
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = _headwise_rmsnorm(y, p["norm"], nh, cfg.norm_eps)
    out = x + jnp.einsum("bsk,kd->bsd", y, p["down"])
    out = lshard(out, "act_batch", "act_res_seq", "act_embed")
    if return_state:
        return out, state
    return out


def mlstm_decode_step(cfg: LMConfig, p: Dict, x: jax.Array, state):
    di, nh, dh = xlstm_dims(cfg)
    b = x.shape[0]
    C, n, m = state
    h = norm(x, p["pre_norm"], "rmsnorm", cfg.norm_eps)
    up = jnp.einsum("bsd,dk->bsk", h, p["up"])
    xi, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bsk,kj->bsj", xi, p["wq"]).reshape(b, nh, dh)
    k = jnp.einsum("bsk,kj->bsj", xi, p["wk"]).reshape(b, nh, dh) / math.sqrt(dh)
    v = jnp.einsum("bsk,kj->bsj", xi, p["wv"]).reshape(b, nh, dh)
    gates = jnp.einsum("bsk,kj->bsj", xi.astype(jnp.float32), p["wif"])[:, 0]
    li, lfr = jnp.split(gates, 2, axis=-1)                     # (b, nh)
    lf = jax.nn.log_sigmoid(lfr + 3.0)
    m_new = jnp.maximum(lf + m, li)
    iw = jnp.exp(li - m_new)
    fw = jnp.exp(lf + m - m_new)
    C = C * fw[..., None, None] + iw[..., None, None] * jnp.einsum(
        "bhd,bhe->bhde", k.astype(jnp.float32), v.astype(jnp.float32))
    n = n * fw[..., None] + iw[..., None] * k.astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), C)
    den = jnp.einsum("bhd,bhd->bh", q.astype(jnp.float32), n)
    y = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    y = y.reshape(b, 1, di).astype(x.dtype) * jax.nn.silu(z)
    y = _headwise_rmsnorm(y, p["norm"], nh, cfg.norm_eps)
    out = x + jnp.einsum("bsk,kd->bsd", y, p["down"])
    return out, (C, n, m_new)


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_defs(cfg: LMConfig) -> Dict[str, ParamDef]:
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    ff = cfg.xlstm.ff_factor_s * d
    dt = cfg.dtype
    return {
        "pre_norm": norm_defs(d, "rmsnorm"),
        "W": ParamDef((d, 4 * d), ("embed", "ssm_inner"), dtype="float32"),
        "R": ParamDef((nh, dh, 4 * dh), ("ssm_heads", None, None),
                      scale=0.5, dtype="float32"),
        "b": ParamDef((4 * d,), ("ssm_inner",), init="zeros", dtype="float32"),
        "ffn_norm": norm_defs(d, "rmsnorm"),
        "ffn": mlp_defs(d, ff, True, dt),
    }


def slstm_cell_scan(cfg: LMConfig, p: Dict, x: jax.Array, init=None):
    """x: (b, s, d). Sequential exponential-gated sLSTM. Returns (y, states)."""
    b, s, d = x.shape
    nh = cfg.n_heads
    dh = d // nh
    pre = jnp.einsum("bsd,dk->bsk", x.astype(jnp.float32), p["W"]) + p["b"]
    if init is None:
        zeros = jnp.zeros((b, nh, dh), jnp.float32)
        init = (zeros, zeros + 1e-6, zeros,
                jnp.full((b, nh, dh), -jnp.inf, jnp.float32))

    def step(carry, u):
        c, n, h, m = carry                                    # (b, nh, dh)
        rec = jnp.einsum("bhd,hdk->bhk", h, p["R"])           # (b, nh, 4dh)
        u = u.reshape(b, nh, 4 * dh) + rec
        i_r, f_r, z_r, o_r = jnp.split(u, 4, axis=-1)
        z = jnp.tanh(z_r)
        o = jax.nn.sigmoid(o_r)
        lf = jax.nn.log_sigmoid(f_r + 3.0)
        m_new = jnp.maximum(lf + m, i_r)
        iw = jnp.exp(i_r - m_new)
        fw = jnp.exp(lf + m - m_new)
        c = fw * c + iw * z
        n = fw * n + iw
        h = o * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    (c, n, h, m), ys = jax.lax.scan(step, init, pre.transpose(1, 0, 2))
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, d)
    return y.astype(x.dtype), (c, n, h, m)


def slstm_block_fwd(cfg: LMConfig, p: Dict, x: jax.Array, init=None,
                    return_state: bool = False):
    h = norm(x, p["pre_norm"], "rmsnorm", cfg.norm_eps)
    y, state = slstm_cell_scan(cfg, p, h, init)
    x = x + y
    h = norm(x, p["ffn_norm"], "rmsnorm", cfg.norm_eps)
    x = x + mlp_fwd(p["ffn"], h, "silu", True)
    x = lshard(x, "act_batch", "act_res_seq", "act_embed")
    if return_state:
        return x, state
    return x


def slstm_decode_step(cfg: LMConfig, p: Dict, x: jax.Array, state):
    h = norm(x, p["pre_norm"], "rmsnorm", cfg.norm_eps)
    y, state = slstm_cell_scan(cfg, p, h, state)
    x = x + y
    h = norm(x, p["ffn_norm"], "rmsnorm", cfg.norm_eps)
    x = x + mlp_fwd(p["ffn"], h, "silu", True)
    return x, state
