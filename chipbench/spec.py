"""Finds a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` at the root of the checkout names them; each is a file
of its own under this directory, so a new cell is new files plus one
``workloads`` entry, and no existing file changes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, fields
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def data_dir() -> Path:
    """Where configurations, traffic mixes, checks and metric readers
    live: this directory (tests point ``HERE`` elsewhere)."""
    return HERE


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be
    resolved from the files."""


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


@dataclass(frozen=True)
class Traffic:
    """A closed loop of waves: one client submits ``wave_jobs`` jobs,
    waits for all of them, then submits the next wave."""
    name: str
    wave_jobs: int
    items_per_job: int
    prompt_len: int
    decode_tokens: int
    batch_jobs: int
    pipeline_depth: int
    groups: str             # the serve CLI's --groups grammar
    tenants: str            # the serve CLI's --tenants grammar
    tenant_shares: str      # "equal" | "zipf:<s>"
    runtimes: int           # 1: serve_jobs; >1: serve_jobs_federated
    check_sequences: int    # served sequences the reference re-reads


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


@dataclass(frozen=True)
class Config:
    name: str
    arch: str               # the program's registry id
    reduced: Dict[str, int]
    sizes: Sizes


def load_benchmark(path: Path = BENCHMARK) -> Dict:
    if not path.is_file():
        raise SpecError(f"no {path.name} at {path.parent}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _read_json(kind: str, name: str) -> Dict:
    path = data_dir() / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind} file {kind}/{name}.json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def cell(bench: Dict, name: str) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(w["name"], w["config"], w["traffic"], int(w["chips"]))
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> Config:
    d = _read_json("configs", name)
    model = dict(d["model"])
    reduced = {k: model[k] for k in d.get("reduced", [])}
    want = {f.name for f in fields(Sizes)}
    if set(model) != want:
        raise SpecError(f"config {name}: model keys {sorted(model)} are not "
                        f"{sorted(want)}")
    return Config(name, d["arch"], reduced, Sizes(**model))


def traffic(name: str) -> Traffic:
    d = _read_json("traffic", name)
    want = {f.name for f in fields(Traffic)} - {"name"}
    if set(d) - {"why"} != want:
        raise SpecError(f"traffic {name}: keys {sorted(d)} are not "
                        f"{sorted(want)} (+ 'why')")
    return Traffic(name=name, **{k: d[k] for k in want})


def check_limit(cell_name: str) -> float:
    """The widest gap, in logits, by which a served token may lie below
    the reference's best token (``checks/<cell>.json``)."""
    return float(_read_json("checks", cell_name)["limit"])


def metric_names(bench: Dict, kind: str, cell_name: str) -> List[str]:
    """The ``kind`` ("end_to_end" or "per_layer") metrics a cell reports:
    those that list the cell under ``workloads``, and those without the
    key, of which a per-layer metric only where the cell reports the
    end-to-end metric it ``moves``."""
    e2e = set(metric_names(bench, "end_to_end", cell_name)) \
        if kind == "per_layer" else set()
    return [m["name"] for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])
            and ("workloads" in m or kind == "end_to_end"
                 or m["moves"] in e2e)]


def metric_unit(bench: Dict, name: str) -> Optional[str]:
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] == name:
                return m["unit"]
    return None
