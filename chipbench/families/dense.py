"""The dense decoder family: stablelm-2 and yi.

A pre-norm block: LayerNorm with bias (stablelm-2) or RMSNorm (yi);
rotary embedding on the first ``rot`` dims of each head (25 % for
stablelm-2, all for yi, theta from the configuration), rotating adjacent
pairs (2i, 2i+1); grouped-query attention in which query head j reads
key/value head j // (n_heads / n_kv_heads); causal softmax scaled by
1/sqrt(head_dim); a SiLU-gated MLP. Departures from the published models:
stablelm-2-1.6b's q/k/v biases are absent here as in the program.

The plain reference is written from the published descriptions,
independent of the program, and draws its weights again from the seed one
layer at a time, so that the largest configuration (yi-6b, 12.1 GB of
bf16 weights) is never held whole in float32. The work counts follow
``chipbench/work.py``'s rules.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HI, SCORE_BYTES, proj
from chipbench.weights import Leaf, Recipe, Redraw
from chipbench.work import BYTES, ZERO, Work


@dataclass(frozen=True)
class Sizes:
    """The model as the configuration file states it: what the weights,
    the work counts and the plain reference are built from."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_type: str          # layernorm | rmsnorm
    norm_eps: float
    act: str                # silu
    gated_mlp: bool
    rope_fraction: float
    rope_theta: float
    tie_embeddings: bool
    dtype: str              # dtype the weights are served in

    @property
    def rot(self) -> int:
        """Rotated dims of each head (partial RoPE)."""
        return int(self.head_dim * self.rope_fraction) // 2 * 2


def sizes_of(cfg) -> Sizes:
    """The program's config as the benchmark's ``Sizes``."""
    d = {f: getattr(cfg, f) for f in Sizes.__dataclass_fields__}
    d["head_dim"] = cfg.resolved_head_dim
    return Sizes(**d)


def program_config(cfg, reduced: Dict):
    return cfg.replace(**reduced)


# -- weights ---------------------------------------------------------------

def recipe(s: Sizes) -> Recipe:
    d, h, g, hd, f = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    L = s.n_layers
    out = {
        "embed": Leaf((s.vocab, d), 0.0, 1.0, True, None),
        "final_norm/scale": Leaf((d,), 1.0, 0.1, False, None),
        "blocks/attn/wq": Leaf((d, h, hd), 0.0, d ** -0.5, True, L),
        "blocks/attn/wk": Leaf((d, g, hd), 0.0, d ** -0.5, True, L),
        "blocks/attn/wv": Leaf((d, g, hd), 0.0, d ** -0.5, True, L),
        "blocks/attn/wo": Leaf((h, hd, d), 0.0, (h * hd) ** -0.5, True, L),
        "blocks/attn_norm/scale": Leaf((d,), 1.0, 0.1, False, L),
        "blocks/mlp_norm/scale": Leaf((d,), 1.0, 0.1, False, L),
        "blocks/mlp/wi": Leaf((d, f), 0.0, d ** -0.5, True, L),
        "blocks/mlp/wo": Leaf((f, d), 0.0, f ** -0.5, True, L),
    }
    if s.gated_mlp:
        out["blocks/mlp/wg"] = Leaf((d, f), 0.0, d ** -0.5, True, L)
    if s.norm_type == "layernorm":
        for n, layers in (("final_norm", None), ("blocks/attn_norm", L),
                          ("blocks/mlp_norm", L)):
            out[f"{n}/bias"] = Leaf((d,), 0.0, 0.05, False, layers)
    if not s.tie_embeddings:
        out["unembed"] = Leaf((d, s.vocab), 0.0, d ** -0.5, True, None)
    return out


# -- the plain reference ---------------------------------------------------

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
    q = _rope(proj("btd,dhk->bthk", h, a["wq"], fp8, 0), s)
    k = _rope(proj("btd,dgk->btgk", h, a["wk"], fp8, 0), s)
    v = proj("btd,dgk->btgk", h, a["wv"], fp8, 0)
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
    up = proj("btd,df->btf", h, m["wi"], fp8, 0)
    if s.gated_mlp:
        up = jax.nn.silu(proj("btd,df->btf", h, m["wg"], fp8, 0)) * up
    else:
        up = jax.nn.silu(up)
    return x + proj("btf,fd->btd", up, m["wo"], fp8, 0)


def _wo(o, wo, fp8: bool):
    """Output projection of the heads: o (b, t, h, hd), wo (h, hd, d)."""
    b, t, h, hd = o.shape
    return proj("btk,kd->btd", o.reshape(b, t, h * hd),
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
        out = proj("btd,dv->btv", x, un, fp8, 0)
        return np.asarray(out)


# -- work counts -----------------------------------------------------------

def block_weights(s: Sizes) -> int:
    """Weight-matrix elements of one decoder layer."""
    d, hd = s.d_model, s.head_dim
    attn = d * s.n_heads * hd * 2 + d * s.n_kv_heads * hd * 2
    mlp = d * s.d_ff * (3 if s.gated_mlp else 2)
    return attn + mlp


def weight_bytes_per_call(s: Sizes) -> int:
    """Weights every call reads: all layers, the norms and the output
    head (the embedding is gathered by row, counted per token)."""
    w = BYTES[s.dtype]
    norms = s.d_model * 4 * (2 * s.n_layers + 1) \
        * (2 if s.norm_type == "layernorm" else 1)
    return s.n_layers * block_weights(s) * w + s.d_model * s.vocab * w \
        + norms


def _kv_bytes_per_position(s: Sizes) -> int:
    return s.n_layers * 2 * s.n_kv_heads * s.head_dim * BYTES[s.dtype]


def prefill(s: Sizes, rows: int, prompt_len: int) -> Work:
    """One prefill call over ``rows`` prompts of ``prompt_len`` tokens;
    logits of the last position only."""
    if rows <= 0:
        return ZERO
    w = BYTES[s.dtype]
    tokens = rows * prompt_len
    mm = 2.0 * block_weights(s) * s.n_layers * tokens
    causal_pairs = prompt_len * (prompt_len + 1) / 2
    attn = 2.0 * 2 * s.n_heads * s.head_dim * causal_pairs * rows \
        * s.n_layers
    head = 2.0 * s.d_model * s.vocab * rows
    data = (weight_bytes_per_call(s)
            + tokens * s.d_model * w                       # embedding rows
            + tokens * _kv_bytes_per_position(s)           # cache written
            + rows * s.vocab * w)                          # logits
    return Work(mm + attn + head, float(data))


def decode(s: Sizes, rows: int, position: int) -> Work:
    """One decode call: ``rows`` sequences each add the token at
    ``position`` (0-based) and attend over positions 0..position."""
    if rows <= 0:
        return ZERO
    w = BYTES[s.dtype]
    ctx = position + 1
    mm = 2.0 * block_weights(s) * s.n_layers * rows
    attn = 2.0 * 2 * s.n_heads * s.head_dim * ctx * rows * s.n_layers
    head = 2.0 * s.d_model * s.vocab * rows
    data = (weight_bytes_per_call(s)
            + rows * s.d_model * w
            + rows * ctx * _kv_bytes_per_position(s)       # cache read
            + rows * _kv_bytes_per_position(s)             # new entry
            + rows * s.vocab * w)
    return Work(mm + attn + head, float(data))


def chunk(s: Sizes, rows: int, prompt_len: int, decode_tokens: int) \
        -> "tuple[Work, Work]":
    """(prefill, decode) work of one served chunk: one prefill call, then
    ``decode_tokens - 1`` decode calls (the first token comes from the
    prefill's logits)."""
    dec = ZERO
    for i in range(decode_tokens - 1):
        dec = dec + decode(s, rows, prompt_len + i)
    return prefill(s, rows, prompt_len), dec
