"""Activation sharding-constraint helpers.

Model code calls :func:`lshard` with *logical* axis names. When a mesh context
is active (set by the launchers via :func:`use_mesh_rules`), this lowers to
``jax.lax.with_sharding_constraint``; otherwise it is a no-op so the same model
code runs un-meshed in unit tests. :func:`per_shard` likewise runs a function
on each shard of the active mesh, or as it is without one.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable, Optional, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding.rules import ShardingRules

_state = threading.local()


def _ctx():
    return getattr(_state, "ctx", None)


@contextmanager
def use_mesh_rules(mesh: Mesh, rules: Optional[ShardingRules] = None):
    prev = _ctx()
    _state.ctx = (mesh, rules or ShardingRules())
    try:
        with mesh:
            yield
    finally:
        _state.ctx = prev


def active_mesh() -> Optional[Mesh]:
    c = _ctx()
    return c[0] if c else None


def active_rules() -> Optional[ShardingRules]:
    c = _ctx()
    return c[1] if c else None


def lshard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the sharding implied by logical ``axes`` (or no-op)."""
    c = _ctx()
    if c is None:
        return x
    mesh, rules = c
    spec = rules.spec(mesh, axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def per_shard(fn: Callable, in_axes: Sequence[Optional[Sequence]],
              out_axes: Sequence) -> Callable:
    """``fn`` run on each shard of the active mesh (or ``fn`` itself).

    ``in_axes`` gives each operand's logical axes (None: replicated) and
    ``out_axes`` the result's. The mesh axes they map to are manual inside
    ``fn`` (``shard_map``), so ``fn`` sees its own rows and heads and no
    collective crosses them; every other mesh axis stays with GSPMD.
    """
    c = _ctx()
    if c is None:
        return fn
    mesh, rules = c

    def run(*args):
        spec = lambda ax, x: rules.spec(mesh, ax, x.shape) if ax else P()
        in_specs = tuple(spec(ax, x) for ax, x in zip(in_axes, args))
        out_specs = spec(out_axes, jax.eval_shape(fn, *args))
        manual = {a for s in (*in_specs, out_specs) for a in _mesh_axes(s)}
        if not manual:
            return fn(*args)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names=manual,
                             check_vma=False)(*args)

    return run


def _mesh_axes(spec: P):
    for e in spec:
        if e is not None:
            yield from (e,) if isinstance(e, str) else e
