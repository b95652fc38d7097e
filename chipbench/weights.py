"""Weights made from the seed, by the benchmark and not by the program.

Every leaf is drawn uniform with a stated mean and standard deviation from
a key folded from the seed, the leaf's name and, for a per-layer leaf, the
layer index. So the serve path gets the whole tree from one jitted call on
the device, in the dtype it is served in, and the plain reference can draw
any one layer again, alone, after the program's state is freed.

Scales keep activations near unit size through the depth (matrices with
standard deviation 1/sqrt(fan-in), embeddings of unit size, norm gains
near 1 and norm biases near 0), so logits are of unit size and a wrong
norm gain, bias, rotation or head grouping moves them visibly.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench.spec import Sizes

#: leaf path -> (shape without the layer axis, mean, std, served in the
#: model dtype (True) or in float32 (False)); per-layer leaves live under
#: "blocks" and gain a leading n_layers axis in the program's tree
Recipe = Dict[str, Tuple[Tuple[int, ...], float, float, bool]]


def recipe(s: Sizes) -> Recipe:
    d, h, g, hd, f = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    out = {
        "embed": ((s.vocab, d), 0.0, 1.0, True),
        "final_norm/scale": ((d,), 1.0, 0.1, False),
        "blocks/attn/wq": ((d, h, hd), 0.0, d ** -0.5, True),
        "blocks/attn/wk": ((d, g, hd), 0.0, d ** -0.5, True),
        "blocks/attn/wv": ((d, g, hd), 0.0, d ** -0.5, True),
        "blocks/attn/wo": ((h, hd, d), 0.0, (h * hd) ** -0.5, True),
        "blocks/attn_norm/scale": ((d,), 1.0, 0.1, False),
        "blocks/mlp_norm/scale": ((d,), 1.0, 0.1, False),
        "blocks/mlp/wi": ((d, f), 0.0, d ** -0.5, True),
        "blocks/mlp/wo": ((f, d), 0.0, f ** -0.5, True),
    }
    if s.gated_mlp:
        out["blocks/mlp/wg"] = ((d, f), 0.0, d ** -0.5, True)
    if s.norm_type == "layernorm":
        for n in ("final_norm", "blocks/attn_norm", "blocks/mlp_norm"):
            out[f"{n}/bias"] = ((d,), 0.0, 0.05, False)
    if not s.tie_embeddings:
        out["unembed"] = ((d, s.vocab), 0.0, d ** -0.5, True)
    return out


def _draw(key, path: str, layer, shape, mean, std, dtype):
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    half = std * math.sqrt(3.0)
    w = jax.random.uniform(key, shape, jnp.float32, mean - half, mean + half)
    return w.astype(dtype)


def _dtype(s: Sizes, in_model_dtype: bool):
    return jnp.dtype(s.dtype) if in_model_dtype else jnp.dtype(jnp.float32)


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        *parents, last = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def _flatten(tree, prefix: str = "") -> Dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def check_layout(s: Sizes, abstract) -> None:
    """The program's parameter tree (shapes and dtypes, from
    ``jax.eval_shape``) has exactly the leaves the recipe draws."""
    want = {}
    for path, (shape, _, _, model_dt) in recipe(s).items():
        if path.startswith("blocks/"):
            shape = (s.n_layers,) + shape
        want[path] = (tuple(shape), _dtype(s, model_dt))
    have = {p: (tuple(a.shape), jnp.dtype(a.dtype))
            for p, a in _flatten(abstract).items()}
    if have != want:
        diff = sorted(set(have.items()) ^ set(want.items()))
        raise ValueError(f"the program's parameter tree differs from the "
                         f"benchmark's recipe: {diff}")


def make_params(s: Sizes, seed: int, device=None) -> Dict:
    """The whole tree in the program's layout, in one jitted call."""
    rec = recipe(s)

    def build(key):
        flat = {}
        for path, (shape, mean, std, model_dt) in rec.items():
            dt = _dtype(s, model_dt)
            if path.startswith("blocks/"):
                flat[path] = jax.vmap(
                    lambda l, path=path, shape=shape, mean=mean, std=std,
                    dt=dt: _draw(key, path, l, shape, mean, std, dt))(
                        jnp.arange(s.n_layers))
            else:
                flat[path] = _draw(key, path, None, shape, mean, std, dt)
        return _nest(flat)

    out = None
    if device is not None:
        out = jax.sharding.SingleDeviceSharding(device)
    # the key is made on the host: a seed may need more than 32 bits
    return jax.jit(build, out_shardings=out)(jax.random.PRNGKey(seed))


def _layer_fn(s: Sizes):
    rec = {p[len("blocks/"):]: r for p, r in recipe(s).items()
           if p.startswith("blocks/")}

    @jax.jit
    def layer(key, l):
        flat = {}
        for name, (shape, mean, std, model_dt) in rec.items():
            flat[name] = _draw(key, "blocks/" + name, l, shape, mean, std,
                               _dtype(s, model_dt)).astype(jnp.float32)
        return _nest(flat)
    return layer


class Redraw:
    """Draws the served values again, in float32, one layer at a time:
    what the plain reference computes with."""

    def __init__(self, s: Sizes, seed: int):
        self.s = s
        self.key = jax.random.PRNGKey(seed)
        self._layer = _layer_fn(s)

    def layer(self, l: int) -> Dict:
        return self._layer(self.key, l)

    def top(self) -> Dict:
        out = {}
        for path, (shape, mean, std, model_dt) in recipe(self.s).items():
            if not path.startswith("blocks/"):
                out[path] = _draw(self.key, path, None, shape, mean, std,
                                  _dtype(self.s, model_dt)) \
                    .astype(jnp.float32)
        return _nest(out)
