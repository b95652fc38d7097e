"""Weights made from the seed, by the benchmark and not by the program.

Every leaf is drawn uniform with a stated mean and standard deviation from
a key folded from the seed, the leaf's name and, for a leaf stacked over
layers, the layer index. So the serve path gets the whole tree from one
jitted call on the device, in the dtype it is served in, and the plain
reference can draw any one layer again, alone, after the program's state
is freed.

Which leaves there are is the family's ``recipe`` (``chipbench/families``);
the family is the one whose ``Sizes`` these functions are handed. Scales
keep activations near unit size through the depth (matrices with standard
deviation 1/sqrt(fan-in), embeddings of unit size, norm gains near 1 and
norm biases near 0), so logits are of unit size and a wrong norm gain,
bias, rotation or head grouping moves them visibly.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from chipbench.spec import family_of


class Leaf(NamedTuple):
    shape: Tuple[int, ...]      # without the layer axis
    mean: float
    std: float
    in_model_dtype: bool        # served in the model dtype, else float32
    layers: Optional[int]       # stacked over this many layers, or None


#: leaf path in the program's tree -> how it is drawn; a stacked leaf
#: gains a leading axis of ``layers`` in the program's tree
Recipe = Dict[str, Leaf]


def _draw(key, path: str, layer, shape, mean, std, dtype):
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    half = std * math.sqrt(3.0)
    w = jax.random.uniform(key, shape, jnp.float32, mean - half, mean + half)
    return w.astype(dtype)


def _dtype(s, in_model_dtype: bool):
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


def check_layout(s, abstract) -> None:
    """The program's parameter tree (shapes and dtypes, from
    ``jax.eval_shape``) has exactly the leaves the recipe draws, each
    stacked over the layers the recipe states."""
    want = {}
    for path, leaf in family_of(s).recipe(s).items():
        shape = leaf.shape if leaf.layers is None \
            else (leaf.layers,) + leaf.shape
        want[path] = (tuple(shape), _dtype(s, leaf.in_model_dtype))
    have = {p: (tuple(a.shape), jnp.dtype(a.dtype))
            for p, a in _flatten(abstract).items()}
    diff = {p: (have.get(p), want.get(p))
            for p in sorted(set(have) | set(want))
            if have.get(p) != want.get(p)}
    if diff:
        raise ValueError(f"the program's parameter tree differs from the "
                         f"benchmark's recipe, leaf: (program, recipe): "
                         f"{diff}")


def make_params(s, seed: int, device=None) -> Dict:
    """The whole tree in the program's layout, in one jitted call."""
    rec = family_of(s).recipe(s)

    def build(key):
        flat = {}
        for path, (shape, mean, std, model_dt, layers) in rec.items():
            dt = _dtype(s, model_dt)
            if layers is not None:
                flat[path] = jax.vmap(
                    lambda l, path=path, shape=shape, mean=mean, std=std,
                    dt=dt: _draw(key, path, l, shape, mean, std, dt))(
                        jnp.arange(layers))
            else:
                flat[path] = _draw(key, path, None, shape, mean, std, dt)
        return _nest(flat)

    out = None
    if device is not None:
        out = jax.sharding.SingleDeviceSharding(device)
    # the key is made on the host: a seed may need more than 32 bits
    return jax.jit(build, out_shardings=out)(jax.random.PRNGKey(seed))


def _layer_fn(s, rec: Recipe, stack: str):
    prefix = stack + "/"
    leaves = {p[len(prefix):]: (p, leaf) for p, leaf in rec.items()
              if p.startswith(prefix) and leaf.layers is not None}

    @jax.jit
    def layer(key, l):
        flat = {}
        for name, (path, (shape, mean, std, model_dt, _)) in leaves.items():
            flat[name] = _draw(key, path, l, shape, mean, std,
                               _dtype(s, model_dt)).astype(jnp.float32)
        return _nest(flat)
    return layer


class Redraw:
    """Draws the served values again, in float32, one layer at a time:
    what the plain reference computes with."""

    def __init__(self, s, seed: int):
        self.s = s
        self.key = jax.random.PRNGKey(seed)
        self.recipe = family_of(s).recipe(s)
        self._layers = {}

    def layer(self, l: int, stack: str = "blocks") -> Dict:
        """Layer ``l`` of the stacked leaves under ``stack/`` (a dense
        model's one stack is ``blocks``), with that prefix taken off."""
        if stack not in self._layers:
            self._layers[stack] = _layer_fn(self.s, self.recipe, stack)
        return self._layers[stack](self.key, l)

    def top(self) -> Dict:
        out = {}
        for path, (shape, mean, std, model_dt, layers) in self.recipe.items():
            if layers is None:
                out[path] = _draw(self.key, path, None, shape, mean, std,
                                  _dtype(self.s, model_dt)) \
                    .astype(jnp.float32)
        return _nest(out)
