"""The one traffic generator: waves of jobs from a traffic file's numbers.

Every seed gets the same work: each wave holds the same number of jobs of
each tenant, apportioned by the mix's shares, and the seed only orders
them. So runs on different seeds differ in what DWRR and the router see
first, not in how much each runtime has to do.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np

from chipbench.spec import Traffic


def shares(spec: str, n: int) -> List[float]:
    """``equal`` or ``zipf:<s>`` over ``n`` tenants, rank order."""
    if spec == "equal":
        w = [1.0] * n
    elif spec.startswith("zipf:"):
        s = float(spec.split(":", 1)[1])
        w = [1.0 / (k + 1) ** s for k in range(n)]
    else:
        raise ValueError(f"unknown tenant shares {spec!r}")
    total = sum(w)
    return [x / total for x in w]


def apportion(total: int, fractions: Sequence[float]) -> List[int]:
    """Whole counts summing to ``total``, by largest remainder."""
    raw = [f * total for f in fractions]
    out = [math.floor(x) for x in raw]
    by_rest = sorted(range(len(raw)), key=lambda i: out[i] - raw[i])
    for i in by_rest[:total - sum(out)]:
        out[i] += 1
    return out


class Waves:
    """The tenants of wave ``w``'s jobs, in submit order."""

    def __init__(self, traffic: Traffic, seed: int, tenants: Sequence[str]):
        counts = apportion(traffic.wave_jobs,
                           shares(traffic.tenant_shares, len(tenants)))
        self.members = [t for t, c in zip(tenants, counts) for _ in range(c)]
        self.seed = seed

    def tenants(self, wave: int) -> List[str]:
        rng = np.random.default_rng([self.seed, wave])
        return [self.members[i] for i in rng.permutation(len(self.members))]


def prompt(seed: int, row: int, vocab: int, length: int) -> np.ndarray:
    """The prompt the serve engine builds for request row ``row``: a copy
    of ``HeteroServeEngine._prompt``, which makes prompts inside the
    program; the reference needs them without asking the program."""
    rng = np.random.Generator(np.random.PCG64((seed << 32) ^ row))
    return rng.integers(0, vocab, length, dtype=np.int32)
