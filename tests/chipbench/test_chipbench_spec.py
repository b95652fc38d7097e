"""BENCHMARK.json resolves, by file name, to what the harness runs."""
import dataclasses
import json
import math
import re
import shutil

import pytest

from chipbench import harness, spec, trace

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.cell(BENCH, cell)
    conf = spec.config(c.config)
    tr = spec.traffic(c.traffic)
    assert 0 < spec.check_limit(cell)
    assert c.chips in (1, 4)
    assert tr.runtimes == (c.chips if c.chips > 1 else 1)
    entry = next(x for x in BENCH["configs"] if x["name"] == c.config)
    assert entry["file"] == f"chipbench/configs/{c.config}.json"
    assert sorted(conf.reduced) == sorted(entry["reduced"])
    for kind in ("end_to_end", "per_layer"):
        for name in spec.metric_names(BENCH, kind, cell):
            if name != "setup_s":
                assert callable(harness.load_reader(name))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_layers_move(cell):
    """Every cell reports ``setup_s``, another end-to-end metric and a
    per-layer one, and each per-layer metric it reports moves one of its
    own end-to-end metrics: the four-chip cell's have names of their own,
    so its noise sets none of the one-chip cells' bounds."""
    e2e = spec.metric_names(BENCH, "end_to_end", cell)
    layers = spec.metric_names(BENCH, "per_layer", cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    assert {moves[name] for name in layers} <= set(e2e)
    fed4 = spec.cell(BENCH, cell).chips > 1
    assert all(n.endswith(".fed4") == fed4 for n in e2e if n != "setup_s")


def test_config_sizes_match_the_program():
    from repro.configs.registry import get_config
    for entry in BENCH["configs"]:
        conf = spec.config(entry["name"])
        harness._check_sizes(get_config(conf.arch).replace(**conf.reduced),
                             conf.sizes)


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.cell(BENCH, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.config("no-such-config")
    with pytest.raises(spec.SpecError):
        harness.load_reader("no_such_metric")


#: a new family as a file of its own: the dense decoder with one model key
#: of its own, ``layers_kept``, which ``program_config`` maps onto the
#: program's ``n_layers``; it notes which of its parts the harness called
TOY_FAMILY = '''
from dataclasses import dataclass, fields

from chipbench.families import dense

CALLS = []


@dataclass(frozen=True)
class Sizes(dense.Sizes):
    layers_kept: int


def sizes_of(cfg):
    d = dense.sizes_of(cfg)
    return Sizes(**{f.name: getattr(d, f.name) for f in fields(d)},
                 layers_kept=cfg.n_layers)


def program_config(cfg, reduced):
    reduced = dict(reduced)
    reduced["n_layers"] = reduced.pop("layers_kept")
    return dense.program_config(cfg, reduced)


def recipe(s):
    return dense.recipe(s)


class Reference(dense.Reference):
    def __init__(self, s, seed):
        CALLS.append("Reference")
        super().__init__(s, seed)


def chunk(s, rows, prompt_len, decode_tokens):
    CALLS.append("chunk")
    return dense.chunk(s, rows, prompt_len, decode_tokens)
'''


def _new_config(data, family):
    """yi-6b's file copied; or a configuration of the toy family, from
    another dense arch of the program's registry, cut to 4 layers."""
    if family is None:
        return json.loads((data / "configs" / "yi-6b.json").read_text())
    from repro.configs.registry import get_config
    (data / "families" / f"{family}.py").write_text(TOY_FAMILY)
    model = dataclasses.asdict(harness.sizes_of(get_config("deepseek-7b")))
    model.update(n_layers=4, layers_kept=4)
    return {"arch": "deepseek-7b", "family": family,
            "reduced": ["layers_kept"], "model": model}


@pytest.mark.parametrize("family", [None, "toy"],
                         ids=["dense-copy", "new-family"])
def test_a_new_cell_is_new_files_and_one_entry(tmp_path, monkeypatch,
                                               family):
    """A cell made of a new configuration (of a new family: one more
    file), traffic mix, check and metric reader, plus one workloads entry,
    runs end to end without an edit to any file that is already there.
    The profiler's summary is stood in for (a CPU trace has no device
    plane), so the traced window's work is counted too."""
    data = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, data, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {p.relative_to(data): p.read_bytes()
              for p in data.rglob("*") if p.is_file()}
    conf = _new_config(data, family)
    (data / "configs" / "new-conf.json").write_text(json.dumps(conf))
    traffic = json.loads((data / "traffic" / "chat-decode.json").read_text())
    traffic.update(wave_jobs=4, batch_jobs=2, prompt_len=8,
                   decode_tokens=3, check_sequences=2)
    (data / "traffic" / "tiny-chat.json").write_text(json.dumps(traffic))
    (data / "checks" / "new-conf.tiny-chat.json").write_text(
        json.dumps({"limit": 1.0}))
    (data / "metrics" / "waves_per_s.py").write_text(
        "def read(run):\n    return run.waves / run.window_s\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "new-conf", "source": "x",
                             "file": "chipbench/configs/new-conf.json",
                             "reduced": conf["reduced"], "why": "x"})
    bench["workloads"].append({"name": "new-conf.tiny-chat",
                               "config": "new-conf",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "waves_per_s", "unit": "1/s",
                               "better": "higher",
                               "source": "host_clock", "layer": "queue",
                               "moves": "output_tokens_per_s",
                               "workloads": ["new-conf.tiny-chat"]})
    monkeypatch.setattr(spec, "HERE", data)
    whole = trace.Summary(window_s=1.0, busy_s=1.0, program_s={},
                          host_window=(-math.inf, math.inf))
    monkeypatch.setattr(trace.Profiler, "summary", lambda self: whole)
    out = harness.run_cell("new-conf.tiny-chat", 2**31 + 5, 0.2, True,
                           0.0, bench=bench, rehearse=True,
                           log=lambda _: None)
    assert out["correct"] and out["failed"] == 0
    assert out["metrics"]["waves_per_s"]["value"] > 0
    if family is not None:
        assert {"Reference", "chunk"} <= set(spec.family(family).CALLS)
    after = {p.relative_to(data): p.read_bytes()
             for p in data.rglob("*") if p.is_file() and
             p.relative_to(data) in before}
    assert after == before
