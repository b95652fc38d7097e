"""Finds a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` at the root of the checkout names them; each is a file
of its own under this directory, and so is the architecture family a
configuration names (``families/<name>.py``), so a new cell is new files
plus one ``workloads`` entry, and no existing file changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import sys
from dataclasses import dataclass, fields
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def data_dir() -> Path:
    """Where configurations, families, traffic mixes, checks and metric
    readers live: this directory (tests point ``HERE`` elsewhere)."""
    return HERE


class SpecError(ValueError):
    """A cell, configuration, family, traffic mix or metric that cannot be
    resolved from the files."""


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
    reduced: Dict[str, Any]
    sizes: Any              # its family's ``Sizes``


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


def family(name: str) -> ModuleType:
    """The family file ``families/<name>.py``: the package's own imported
    as ``chipbench.families.<name>``, another directory's (a test's copy)
    loaded once under a module name of its own."""
    path = data_dir() / "families" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no family file families/{name}.py (looked for "
                        f"{path})")
    if path == Path(__file__).resolve().parent / "families" / f"{name}.py":
        return importlib.import_module(f"chipbench.families.{name}")
    module_name = f"chipbench.families.{name}@{path.parent}"
    if module_name not in sys.modules:
        module_spec = importlib.util.spec_from_file_location(module_name,
                                                             path)
        module = importlib.util.module_from_spec(module_spec)
        sys.modules[module_name] = module
        try:
            module_spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[module_name]
            raise
    return sys.modules[module_name]


def family_of(sizes) -> ModuleType:
    """The family whose ``Sizes`` ``sizes`` is: the weights, work counts
    and reference that belong to it."""
    return sys.modules[type(sizes).__module__]


def config(name: str) -> Config:
    d = _read_json("configs", name)
    fam = family(d.get("family", "dense"))
    model = dict(d["model"])
    want = {f.name for f in fields(fam.Sizes)}
    if set(model) != want:
        raise SpecError(f"config {name}: model keys {sorted(model)} are not "
                        f"{sorted(want)}")
    unknown = sorted(set(d.get("reduced", [])) - want)
    if unknown:
        raise SpecError(f"config {name}: reduced keys {unknown} are not "
                        f"model keys")
    reduced = {k: model[k] for k in d.get("reduced", [])}
    return Config(name, d["arch"], reduced, fam.Sizes(**model))


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


def __getattr__(name):
    """The dense family's ``Sizes``, under the name it had here."""
    if name == "Sizes":
        from chipbench.families.dense import Sizes
        return Sizes
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
