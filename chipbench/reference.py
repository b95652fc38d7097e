"""What every family's plain float32 reference shares, its fp8 control,
and the gaps the check compares.

Each family's ``Reference`` (``chipbench/families/<name>.py``) is written
from the published descriptions, independent of the program: it imports
nothing of ``repro`` and takes its weights from ``chipbench.weights.Redraw``,
drawn again from the seed one layer at a time.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise computed in bfloat16 passes. ``fp8=True`` is the
control: each weight and each activation entering a weight product is
rounded to float8 (e4m3; weights scaled per output channel, activations
per row), the precision a later change might be tempted to serve in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
#: attention scores of one row block are kept under this many bytes
SCORE_BYTES = 256 * 2 ** 20


def round_fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def proj(spec: str, x, w, fp8: bool, w_in_axes):
    """``einsum(spec, x, w)`` for a weight product; in the control both
    sides are rounded to fp8 first (activations per row, weights per
    output channel, i.e. over the contracted ``w_in_axes``)."""
    if fp8:
        x = round_fp8(x, axis=-1)
        w = round_fp8(w, axis=w_in_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def served_gaps(ref, prompts: np.ndarray, served: np.ndarray) \
        -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position: (b, decode) float32, 0 where the
    served token is the reference's own greedy choice."""
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    lg = ref.logits(seq, prompts.shape[1] - 1)
    picked = np.take_along_axis(lg, served[..., None].astype(np.int64), -1)
    return lg.max(-1) - picked[..., 0]


def control_gaps(ref, prompts: np.ndarray, served: np.ndarray) \
        -> np.ndarray:
    """The control: at each position of the same sequences, the gap of
    the token that the fp8 reference puts first."""
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    first = prompts.shape[1] - 1
    lg = ref.logits(seq, first)
    pick = ref.logits(seq, first, fp8=True).argmax(-1)
    picked = np.take_along_axis(lg, pick[..., None], -1)
    return lg.max(-1) - picked[..., 0]


def __getattr__(name):
    """The dense family's reference, under the name it had here."""
    if name == "Reference":
        from chipbench.families.dense import Reference
        return Reference
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
