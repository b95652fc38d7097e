"""Operations and bytes that a prefill or a decode call needs, from shapes.

Each family counts its own (``chunk`` in ``chipbench/families/<name>.py``)
by these rules, into the ``Work`` defined here. Counts are of the work the
algorithm needs, not of what the program happens to compute: the rows a
chunk really holds (not its padded batch bucket), causal attention over
the positions that exist (not the padded cache), and every weight read
once per call. A roofline share from these
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

#: bytes per element of each dtype weights are served in
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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

_DENSE = ("prefill", "decode", "chunk", "block_weights",
          "weight_bytes_per_call")


def __getattr__(name):
    """The dense family's counts, under the names they had here."""
    if name in _DENSE:
        from chipbench.families import dense
        return getattr(dense, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
