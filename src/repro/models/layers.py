"""Shared layers: param-spec system, norms, activations, RoPE, MLP."""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamDef(NamedTuple):
    """Declarative parameter: shape + logical sharding axes + initializer."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | small_normal
    scale: float = 1.0            # stddev multiplier for normal inits
    dtype: str = "bfloat16"


def is_param_def(x) -> bool:
    return isinstance(x, ParamDef)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _scaled_normal(key: jax.Array, shape, std: float, dtype):
    # one program per leaf: run eagerly as three ops, the float32 draw and
    # its scaled copy of every leaf could sit in device memory at once
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_from_defs(defs, key: jax.Array):
    """Materialize a pytree of ParamDef into concrete arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_param_def)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, d in zip(keys, leaves):
        dt = jnp.dtype(d.dtype)
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dt))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dt))
        else:
            fan_in = d.shape[0] if d.shape else 1
            std = d.scale / math.sqrt(max(fan_in, 1))
            out.append(_scaled_normal(k, d.shape, std, dt))
    return jax.tree.unflatten(treedef, out)


def abstract_from_defs(defs):
    """ShapeDtypeStruct tree (no allocation) — used by the dry-run."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=is_param_def)


def axes_from_defs(defs):
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=is_param_def)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)) \
        .astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def norm(x, p: Dict, kind: str, eps: float):
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"], eps)
    return rmsnorm(x, p["scale"], eps)


def norm_defs(d_model: int, kind: str) -> Dict[str, ParamDef]:
    out = {"scale": ParamDef((d_model,), ("norm",), "ones", dtype="float32")}
    if kind == "layernorm":
        out["bias"] = ParamDef((d_model,), ("norm",), "zeros", dtype="float32")
    return out


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE (with partial-rotary support, e.g. stablelm rope_fraction=0.25)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, fraction: float, theta: float):
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    return jnp.asarray(inv, jnp.float32), rot


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
               rot: int) -> jax.Array:
    """x: (..., seq, n_heads, head_dim); positions: broadcastable to (..., seq)."""
    if rot == 0:
        return x
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., s, rot/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1) \
        if x_pass.shape[-1] else rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int, gated: bool, dtype: str):
    out = {
        "wi": ParamDef((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "wo": ParamDef((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }
    if gated:
        out["wg"] = ParamDef((d_model, d_ff), ("embed", "mlp"), dtype=dtype)
    return out


def mlp_fwd(p: Dict, x: jax.Array, act: str, gated: bool) -> jax.Array:
    from repro.sharding.partition import lshard
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    if gated:
        h = act_fn(act)(jnp.einsum("...d,df->...f", x, p["wg"])) * h
    else:
        h = act_fn(act)(h)
    h = lshard(h, "act_batch", "act_seq", "act_mlp")
    return jnp.einsum("...f,fd->...d", h, p["wo"])
