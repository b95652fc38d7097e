"""The hybrid family: granite-4.0-h, Mamba-2 and attention layers in a
published pattern, each with an MLP of its own.

Layer l is the mixer ``layer_types[l]`` names, followed by a SiLU-gated
MLP; both branches are scaled by the residual multiplier r:

    h = h + r * mixer(RMSNorm(h));   h = h + r * MLP(RMSNorm(h))

Token embeddings are multiplied by ``embedding_multiplier``; the logits
are RMSNorm(h) @ embed^T / ``logits_scaling`` (tied embeddings).

Attention: no positional embedding (NoPE); causal softmax of q.k scaled by
``attention_multiplier``; query head j reads key/value head
j // (n_heads / n_kv_heads).

Mamba-2 (arXiv:2405.21060): one input projection to z, x, B, C and dt; a
depthwise causal convolution of width ``ssm_conv`` with bias over (x, B,
C), then SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log); the
state-space dual in its quadratic masked form (§3 there),
y = (L o C B^T)(dt x) + D x with L_ij = exp(sum_{j<k<=i} dt_k A) for
j <= i, the heads of one group sharing B and C; the gated RMSNorm,
RMSNorm(y * SiLU(z)) * w; the output projection. This is not the
program's chunked scan: the reference computes each output from every
earlier position directly, in blocks of query positions.

Departures from the published model: weights are drawn from the seed;
``A_log`` and ``dt_bias`` are drawn from ranges (under the
configuration's ``assumed``). The reference draws each layer again from
the seed, one layer of one stack at a time, as ``chipbench/weights.py``
does; the work counts follow ``chipbench/work.py``'s rules.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HI, SCORE_BYTES, proj
from chipbench.weights import Leaf, Recipe, Redraw
from chipbench.work import BYTES, ZERO, Work

MAMBA, ATTENTION = "mamba", "attention"
#: A = exp(A_log) and dt = softplus(dt_bias) are drawn uniform over these
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


@dataclass(frozen=True)
class Sizes:
    """The model as the configuration file states it."""
    n_layers: int
    layer_types: Tuple[str, ...]   # "mamba" | "attention", one per layer
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    ssm_state: int                 # d_state
    ssm_conv: int                  # d_conv
    ssm_expand: int
    ssm_head_dim: int
    ssm_groups: int                # B/C groups
    ssm_chunk: int                 # the SSD chunk the program scans by
    norm_eps: float
    act: str                       # silu
    tie_embeddings: bool
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    dtype: str                     # dtype the weights are served in

    def __post_init__(self):
        # a configuration file gives a list
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attn(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


_SSM_FIELDS = {"ssm_state": "d_state", "ssm_conv": "d_conv",
               "ssm_expand": "expand", "ssm_head_dim": "head_dim",
               "ssm_groups": "n_groups", "ssm_chunk": "chunk_size"}


def sizes_of(cfg) -> Sizes:
    """The program's config as the benchmark's ``Sizes``."""
    d = {f.name: getattr(cfg, f.name, None) for f in dataclasses.fields(Sizes)}
    d.update({k: getattr(cfg.ssm, v) for k, v in _SSM_FIELDS.items()})
    d["layer_types"] = tuple(cfg.hybrid.layer_types)
    d["head_dim"] = cfg.resolved_head_dim
    return Sizes(**d)


def program_config(cfg, reduced: Dict):
    """``reduced`` applied: ``ssm_*`` keys to the program's ``SSMConfig``,
    ``layer_types`` to its ``HybridConfig``."""
    top = {k: v for k, v in reduced.items()
           if k not in _SSM_FIELDS and k != "layer_types"}
    ssm = {_SSM_FIELDS[k]: v for k, v in reduced.items() if k in _SSM_FIELDS}
    if ssm:
        top["ssm"] = dataclasses.replace(cfg.ssm, **ssm)
    if "layer_types" in reduced:
        top["hybrid"] = dataclasses.replace(
            cfg.hybrid, layer_types=tuple(reduced["layer_types"]))
    return cfg.replace(**top)


# -- weights ---------------------------------------------------------------

def _uniform(lo: float, hi: float) -> Tuple[float, float]:
    """(mean, std) of the uniform distribution over [lo, hi]."""
    return (lo + hi) / 2, (hi - lo) / math.sqrt(12.0)


def _inv_softplus(y: float) -> float:
    return math.log(math.expm1(y))


def recipe(s: Sizes) -> Recipe:
    d, h, g, hd, f = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    di, nh, cd = s.d_inner, s.ssm_heads, s.conv_dim
    M, A, L = s.n_mamba, s.n_attn, s.n_layers
    a_log = _uniform(*(math.log(a) for a in A_RANGE))
    dt_bias = _uniform(*(_inv_softplus(t) for t in DT_RANGE))
    # Scaled embedding rows of unit norm, small beside the layers' outputs:
    # with the head tied, the input token's own row would otherwise win the
    # logits by ~sqrt(d) standard deviations at every position, whatever
    # the layers compute. The final norm's gain carries both multipliers
    # back, so that logits are of unit size.
    emb_std = d ** -0.5 / s.embedding_multiplier
    gain = s.logits_scaling * s.embedding_multiplier
    out = {
        "embed": Leaf((s.vocab, d), 0.0, emb_std, True, None),
        "final_norm/scale": Leaf((d,), gain, 0.1 * gain, False, None),
        "mamba/pre_norm": Leaf((d,), 1.0, 0.1, False, M),
        "mamba/in_proj": Leaf((d, 2 * di + 2 * s.ssm_groups * s.ssm_state
                               + nh), 0.0, d ** -0.5, True, M),
        "mamba/conv_w": Leaf((s.ssm_conv, cd), 0.0, s.ssm_conv ** -0.5,
                             True, M),
        "mamba/conv_b": Leaf((cd,), 0.0, 0.1, True, M),
        "mamba/A_log": Leaf((nh,), *a_log, False, M),
        "mamba/dt_bias": Leaf((nh,), *dt_bias, False, M),
        "mamba/D": Leaf((nh,), 1.0, 0.3, False, M),
        "mamba/norm": Leaf((di,), 1.0, 0.1, False, M),
        "mamba/out_proj": Leaf((di, d), 0.0, di ** -0.5, True, M),
        "attn/norm/scale": Leaf((d,), 1.0, 0.1, False, A),
        "attn/wq": Leaf((d, h, hd), 0.0, d ** -0.5, True, A),
        "attn/wk": Leaf((d, g, hd), 0.0, d ** -0.5, True, A),
        "attn/wv": Leaf((d, g, hd), 0.0, d ** -0.5, True, A),
        "attn/wo": Leaf((h, hd, d), 0.0, (h * hd) ** -0.5, True, A),
        "mlp/norm/scale": Leaf((d,), 1.0, 0.1, False, L),
        "mlp/wi": Leaf((d, f), 0.0, d ** -0.5, True, L),
        "mlp/wg": Leaf((d, f), 0.0, d ** -0.5, True, L),
        "mlp/wo": Leaf((f, d), 0.0, f ** -0.5, True, L),
    }
    if not s.tie_embeddings:
        out["unembed"] = Leaf((d, s.vocab), 0.0, d ** -0.5, True, None)
    return out


# -- the plain reference ---------------------------------------------------

def _rms(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _conv(x, w, b):
    """Depthwise causal convolution: out_t = b + sum_k w_k x_{t-K+1+k}.
    x: (b, t, ch); w: (K, ch)."""
    K, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[k] * xp[:, k:k + t] for k in range(K))


def ssd(x, dt, A, B, C, block: int):
    """The SSD's quadratic masked form, y = (L o C B^T)(dt x), in blocks of
    ``block`` query positions. x: (b, t, nh, hd); dt: (b, t, nh); A: (nh,);
    B, C: (b, t, g, n). Returns y: (b, t, nh, hd)."""
    b, t, nh, hd = x.shape
    rep = nh // B.shape[2]
    cum = jnp.cumsum(dt * A, axis=1).transpose(0, 2, 1)          # (b, nh, t)
    u = dt[..., None] * x
    out = []
    for i0 in range(0, t, block):
        i1 = min(t, i0 + block)
        cb = jnp.einsum("bign,bjgn->bgij", C[:, i0:i1], B[:, :i1],
                        precision=HI)
        cb = jnp.repeat(cb, rep, axis=1)                         # per head
        seg = cum[:, :, i0:i1, None] - cum[:, :, None, :i1]
        causal = np.arange(i0, i1)[:, None] >= np.arange(i1)[None, :]
        L = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        out.append(jnp.einsum("bhij,bjhp->bihp", L * cb, u[:, :i1],
                              precision=HI))
    return jnp.concatenate(out, 1)


def _mamba(x, w: Dict, s: Sizes, fp8: bool, block: int):
    """One Mamba-2 mixer over x: (b, t, d), without the residual."""
    b, t, _ = x.shape
    di, nh, gn = s.d_inner, s.ssm_heads, s.ssm_groups * s.ssm_state
    zx = proj("btd,dk->btk", x, w["in_proj"], fp8, 0)
    z, xbc, dt = zx[..., :di], zx[..., di:2 * di + 2 * gn], \
        zx[..., 2 * di + 2 * gn:]
    xbc = jax.nn.silu(_conv(xbc, w["conv_w"], w["conv_b"]))
    xs = xbc[..., :di].reshape(b, t, nh, s.ssm_head_dim)
    B = xbc[..., di:di + gn].reshape(b, t, s.ssm_groups, s.ssm_state)
    C = xbc[..., di + gn:].reshape(b, t, s.ssm_groups, s.ssm_state)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(w["A_log"]), B, C, block) \
        + w["D"][:, None] * xs
    y = _rms(y.reshape(b, t, di) * jax.nn.silu(z), w["norm"], s.norm_eps)
    return proj("btk,kd->btd", y, w["out_proj"], fp8, 0)


def _attention(x, w: Dict, s: Sizes, fp8: bool):
    """One attention layer over x: (b, t, d), without the residual."""
    q = proj("btd,dhk->bthk", x, w["wq"], fp8, 0)
    k = proj("btd,dgk->btgk", x, w["wk"], fp8, 0)
    v = proj("btd,dgk->btgk", x, w["wv"], fp8, 0)
    rep = s.n_heads // s.n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    t = x.shape[1]
    sc = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) \
        * s.attention_multiplier
    causal = np.tril(np.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), -1)
    o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HI)
    b, _, h, hd = o.shape
    return proj("btk,kd->btd", o.reshape(b, t, h * hd),
                w["wo"].reshape(h * hd, -1), fp8, 0)


def _mlp(x, w: Dict, s: Sizes, fp8: bool):
    h = _rms(x, w["norm"]["scale"], s.norm_eps)
    up = proj("btd,df->btf", h, w["wi"], fp8, 0)
    up = jax.nn.silu(proj("btd,df->btf", h, w["wg"], fp8, 0)) * up
    return proj("btf,fd->btd", up, w["wo"], fp8, 0)


def _layer(x, mixer: Dict, mlp: Dict, s: Sizes, fp8: bool, kind: str,
           block: int):
    r = s.residual_multiplier
    if kind == MAMBA:
        x = x + r * _mamba(_rms(x, mixer["pre_norm"], s.norm_eps), mixer, s,
                           fp8, block)
    else:
        x = x + r * _attention(_rms(x, mixer["norm"]["scale"], s.norm_eps),
                               mixer, s, fp8)
    return x + r * _mlp(x, mlp, s, fp8)


class Reference:
    """Logits of a batch of whole sequences, layer by layer."""

    def __init__(self, s: Sizes, seed: int):
        self.s = s
        self.redraw = Redraw(s, seed)
        self._top = None
        self._layers = {}

    def top(self) -> Dict:
        if self._top is None:
            self._top = self.redraw.top()
        return self._top

    def _fn(self, kind: str, fp8: bool, block: int):
        key = (kind, fp8, block)
        if key not in self._layers:
            self._layers[key] = jax.jit(
                lambda x, m, l: _layer(x, m, l, self.s, fp8, kind, block))
        return self._layers[key]

    def logits(self, tokens: np.ndarray, first: int, fp8: bool = False) \
            -> np.ndarray:
        """tokens: (b, t) ids; logits of positions first..t-1, as
        (b, t - first, vocab) float32 on the host."""
        s = self.s
        top = self.top()
        x = jnp.take(top["embed"], jnp.asarray(tokens), axis=0) \
            * s.embedding_multiplier
        b, t = tokens.shape
        rows = max(1, min(b, SCORE_BYTES // (4 * s.n_heads * t * t)))
        block = max(1, SCORE_BYTES // (4 * rows * s.ssm_heads * t))
        seen = {MAMBA: 0, ATTENTION: 0}
        for layer, kind in enumerate(s.layer_types):
            stack = "mamba" if kind == MAMBA else "attn"
            mixer = self.redraw.layer(seen[kind], stack)
            seen[kind] += 1
            mlp = self.redraw.layer(layer, "mlp")
            fn = self._fn(kind, fp8, block)
            x = jnp.concatenate([fn(x[i:i + rows], mixer, mlp)
                                 for i in range(0, b, rows)], 0)
        x = _rms(x[:, first:], top["final_norm"]["scale"], s.norm_eps)
        un = top["embed"].T if s.tie_embeddings else top["unembed"]
        out = proj("btd,dv->btv", x, un, fp8, 0) / s.logits_scaling
        return np.asarray(out)


# -- work counts -----------------------------------------------------------

def weight_bytes_per_call(s: Sizes) -> int:
    """Every leaf of the recipe, once: a call reads all the weights, and
    the tied embedding whole as the output head (the rows it gathers as
    embeddings are counted per token)."""
    total = 0
    for leaf in recipe(s).values():
        n = math.prod(leaf.shape) * (leaf.layers or 1)
        total += n * (BYTES[s.dtype] if leaf.in_model_dtype else 4)
    return total


def _matmul_weights(s: Sizes) -> int:
    """Weight-matrix elements every token multiplies, all layers and the
    output head."""
    d, hd, di = s.d_model, s.head_dim, s.d_inner
    proj_out = 2 * di + 2 * s.ssm_groups * s.ssm_state + s.ssm_heads
    mamba = d * proj_out + di * d
    attn = d * s.n_heads * hd * 2 + d * s.n_kv_heads * hd * 2
    mlp = 3 * d * s.d_ff
    return s.n_mamba * mamba + s.n_attn * attn + s.n_layers * mlp


def _state_bytes(s: Sizes) -> int:
    """One row's decode state of every Mamba-2 layer: the float32 SSM
    state and the conv window, in the served dtype."""
    ssm = s.ssm_heads * s.ssm_head_dim * s.ssm_state * 4
    conv = (s.ssm_conv - 1) * s.conv_dim * BYTES[s.dtype]
    return s.n_mamba * (ssm + conv)


def _kv_bytes_per_position(s: Sizes) -> int:
    return s.n_attn * 2 * s.n_kv_heads * s.head_dim * BYTES[s.dtype]


def _ssd_flops(s: Sizes, length: int) -> float:
    """One row's chunked SSD in one layer over ``length`` positions, in
    chunks of ``ssm_chunk``: inside each chunk C.B per group and the
    weighted sum of x per head over causal pairs; the state's
    contribution to every position after the first chunk; the state
    update from every position."""
    nh, p, n, g = s.ssm_heads, s.ssm_head_dim, s.ssm_state, s.ssm_groups
    Q = s.ssm_chunk
    pairs = sum(q * (q + 1) / 2 for q in
                [Q] * (length // Q) + ([length % Q] if length % Q else []))
    intra = 2.0 * pairs * (g * n + nh * p)
    inter = 2.0 * nh * p * n * max(0, length - Q)
    update = 2.0 * nh * p * n * length
    return intra + inter + update


def prefill(s: Sizes, rows: int, prompt_len: int) -> Work:
    """One prefill call over ``rows`` prompts of ``prompt_len`` tokens;
    logits of the last position only; the state and keys/values written."""
    if rows <= 0:
        return ZERO
    w = BYTES[s.dtype]
    tokens = rows * prompt_len
    mm = 2.0 * (_matmul_weights(s)) * tokens
    ssd_f = s.n_mamba * rows * _ssd_flops(s, prompt_len)
    pairs = prompt_len * (prompt_len + 1) / 2
    attn = 2.0 * 2 * s.n_heads * s.head_dim * pairs * rows * s.n_attn
    head = 2.0 * s.d_model * s.vocab * rows
    data = (weight_bytes_per_call(s)
            + tokens * s.d_model * w                       # embedding rows
            + rows * _state_bytes(s)                       # state written
            + tokens * _kv_bytes_per_position(s)           # cache written
            + rows * s.vocab * w)                          # logits
    return Work(mm + ssd_f + attn + head, float(data))


def decode(s: Sizes, rows: int, position: int) -> Work:
    """One decode call: ``rows`` sequences each add the token at
    ``position`` (0-based): every state read and written once, and
    attention over positions 0..position."""
    if rows <= 0:
        return ZERO
    w = BYTES[s.dtype]
    ctx = position + 1
    mm = 2.0 * _matmul_weights(s) * rows
    # state update (dt x B^T added to the decayed state) and C . state
    ssd_f = 4.0 * s.ssm_heads * s.ssm_head_dim * s.ssm_state * s.n_mamba \
        * rows
    attn = 2.0 * 2 * s.n_heads * s.head_dim * ctx * rows * s.n_attn
    head = 2.0 * s.d_model * s.vocab * rows
    data = (weight_bytes_per_call(s)
            + rows * s.d_model * w
            + 2 * rows * _state_bytes(s)                   # read, written
            + rows * ctx * _kv_bytes_per_position(s)       # cache read
            + rows * _kv_bytes_per_position(s)             # new entry
            + rows * s.vocab * w)
    return Work(mm + ssd_f + attn + head, float(data))


def chunk(s: Sizes, rows: int, prompt_len: int, decode_tokens: int) \
        -> "tuple[Work, Work]":
    """(prefill, decode) work of one served chunk: one prefill call, then
    ``decode_tokens - 1`` decode calls."""
    dec = ZERO
    for i in range(decode_tokens - 1):
        dec = dec + decode(s, rows, prompt_len + i)
    return prefill(s, rows, prompt_len), dec
