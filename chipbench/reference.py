"""Plain float32 reference of the dense decoder, and its fp8 control.

Written from the published descriptions, independent of the program: it
imports nothing of ``repro`` and takes its weights from
``chipbench.weights.Redraw``, drawn again from the seed one layer at a
time, so that the largest configuration (yi-6b, 12.1 GB of bf16 weights)
is never held whole in float32.

A pre-norm block: LayerNorm with bias (stablelm-2) or RMSNorm (yi);
rotary embedding on the first ``rot`` dims of each head (25 % for
stablelm-2, all for yi, theta from the configuration), rotating adjacent
pairs (2i, 2i+1); grouped-query attention in which query head j reads
key/value head j // (n_heads / n_kv_heads); causal softmax scaled by
1/sqrt(head_dim); a SiLU-gated MLP. Departures from the published models:
stablelm-2-1.6b's q/k/v biases are absent here as in the program.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise computed in bfloat16 passes. ``fp8=True`` is the
control: each weight and each activation entering a weight product is
rounded to float8 (e4m3; weights scaled per output channel, activations
per row), the precision a later change might be tempted to serve in.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.spec import Sizes
from chipbench.weights import Redraw

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
#: attention scores of one row block are kept under this many bytes
SCORE_BYTES = 256 * 2 ** 20


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _proj(spec: str, x, w, fp8: bool, w_in_axes):
    """``einsum(spec, x, w)`` for a weight product; in the control both
    sides are rounded to fp8 first (activations per row, weights per
    output channel, i.e. over the contracted ``w_in_axes``)."""
    if fp8:
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=w_in_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _norm(x, p: Dict, s: Sizes):
    if s.norm_type == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + s.norm_eps) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + s.norm_eps) * p["scale"]


def _rope(x, s: Sizes):
    """x: (b, t, heads, head_dim), positions 0..t-1."""
    rot = s.rot
    if rot == 0:
        return x
    t = x.shape[1]
    inv = 1.0 / (s.rope_theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                  / rot))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], -1).reshape(x[..., :rot].shape)
    return jnp.concatenate([rotated, x[..., rot:]], -1)


def _block(x, w: Dict, s: Sizes, fp8: bool):
    """One decoder layer over x: (b, t, d) float32."""
    h = _norm(x, w["attn_norm"], s)
    a = w["attn"]
    q = _rope(_proj("btd,dhk->bthk", h, a["wq"], fp8, 0), s)
    k = _rope(_proj("btd,dgk->btgk", h, a["wk"], fp8, 0), s)
    v = _proj("btd,dgk->btgk", h, a["wv"], fp8, 0)
    rep = s.n_heads // s.n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    t = x.shape[1]
    sc = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) \
        / math.sqrt(s.head_dim)
    causal = np.tril(np.ones((t, t), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HI)
    x = x + _wo(o, a["wo"], fp8)
    h = _norm(x, w["mlp_norm"], s)
    m = w["mlp"]
    up = _proj("btd,df->btf", h, m["wi"], fp8, 0)
    if s.gated_mlp:
        up = jax.nn.silu(_proj("btd,df->btf", h, m["wg"], fp8, 0)) * up
    else:
        up = jax.nn.silu(up)
    return x + _proj("btf,fd->btd", up, m["wo"], fp8, 0)


def _wo(o, wo, fp8: bool):
    """Output projection of the heads: o (b, t, h, hd), wo (h, hd, d)."""
    b, t, h, hd = o.shape
    return _proj("btk,kd->btd", o.reshape(b, t, h * hd),
                 wo.reshape(h * hd, -1), fp8, 0)


class Reference:
    """Logits of a batch of whole sequences, layer by layer."""

    def __init__(self, s: Sizes, seed: int):
        self.s = s
        self.redraw = Redraw(s, seed)
        self._top = None
        self._blocks = {fp8: jax.jit(lambda x, w, fp8=fp8:
                                     _block(x, w, s, fp8))
                        for fp8 in (False, True)}

    def top(self) -> Dict:
        if self._top is None:
            self._top = self.redraw.top()
        return self._top

    def logits(self, tokens: np.ndarray, first: int, fp8: bool = False) \
            -> np.ndarray:
        """tokens: (b, t) ids; logits of positions first..t-1, as
        (b, t - first, vocab) float32 on the host."""
        s = self.s
        top = self.top()
        x = jnp.take(top["embed"], jnp.asarray(tokens), axis=0)
        b, t = tokens.shape
        rows = max(1, SCORE_BYTES // (4 * s.n_heads * t * t))
        block = self._blocks[fp8]
        for layer in range(s.n_layers):
            w = self.redraw.layer(layer)
            x = jnp.concatenate([block(x[i:i + rows], w)
                                 for i in range(0, b, rows)], 0)
        x = _norm(x[:, first:], top["final_norm"], s)
        un = top["embed"].T if s.tie_embeddings else top["unembed"]
        out = _proj("btd,dv->btv", x, un, fp8, 0)
        return np.asarray(out)


def served_gaps(ref: Reference, prompts: np.ndarray, served: np.ndarray) \
        -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position: (b, decode) float32, 0 where the
    served token is the reference's own greedy choice."""
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    lg = ref.logits(seq, prompts.shape[1] - 1)
    picked = np.take_along_axis(lg, served[..., None].astype(np.int64), -1)
    return lg.max(-1) - picked[..., 0]


def control_gaps(ref: Reference, prompts: np.ndarray, served: np.ndarray) \
        -> np.ndarray:
    """The control: at each position of the same sequences, the gap of
    the token that the fp8 reference puts first."""
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    first = prompts.shape[1] - 1
    lg = ref.logits(seq, first)
    pick = ref.logits(seq, first, fp8=True).argmax(-1)
    picked = np.take_along_axis(lg, pick[..., None], -1)
    return lg.max(-1) - picked[..., 0]
