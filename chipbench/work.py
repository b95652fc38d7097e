"""Operations and bytes that a prefill or a decode call needs, from shapes.

Counts are of the work the algorithm needs, not of what the program
happens to compute: the rows a chunk really holds (not its padded batch
bucket), causal attention over the positions that exist (not the padded
cache), and every weight read once per call. A roofline share from these
counts is therefore at most 100 % however the program pads. Matrix
products count 2 operations per multiply-add; norms, softmax and RoPE are
left out (under 1 % of the operations at these widths).

Bytes are the least that must cross HBM: the weights (in the dtype they
are served in), the key/value cache read and written, the embedding rows
gathered and the logits written. Activations between layers are taken to
stay on chip.
"""
from __future__ import annotations

from dataclasses import dataclass

from chipbench.spec import Sizes

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peak_flops: float, peak_bytes_per_s: float) \
            -> float:
        """The roofline: the larger of the compute and the memory bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)

    def bound(self, peak_flops: float, peak_bytes_per_s: float) -> str:
        return "flops" if self.flops / peak_flops \
            >= self.bytes / peak_bytes_per_s else "bytes"


ZERO = Work(0.0, 0.0)


def block_weights(s: Sizes) -> int:
    """Weight-matrix elements of one decoder layer."""
    d, hd = s.d_model, s.head_dim
    attn = d * s.n_heads * hd * 2 + d * s.n_kv_heads * hd * 2
    mlp = d * s.d_ff * (3 if s.gated_mlp else 2)
    return attn + mlp


def weight_bytes_per_call(s: Sizes) -> int:
    """Weights every call reads: all layers, the norms and the output
    head (the embedding is gathered by row, counted per token)."""
    w = _BYTES[s.dtype]
    norms = s.d_model * 4 * (2 * s.n_layers + 1) \
        * (2 if s.norm_type == "layernorm" else 1)
    return s.n_layers * block_weights(s) * w + s.d_model * s.vocab * w \
        + norms


def _kv_bytes_per_position(s: Sizes) -> int:
    return s.n_layers * 2 * s.n_kv_heads * s.head_dim * _BYTES[s.dtype]


def prefill(s: Sizes, rows: int, prompt_len: int) -> Work:
    """One prefill call over ``rows`` prompts of ``prompt_len`` tokens;
    logits of the last position only."""
    if rows <= 0:
        return ZERO
    w = _BYTES[s.dtype]
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
    w = _BYTES[s.dtype]
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
