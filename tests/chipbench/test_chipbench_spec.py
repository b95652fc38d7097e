"""BENCHMARK.json resolves, by file name, to what the harness runs."""
import json
import re
import shutil

import pytest

from chipbench import harness, spec

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


def test_a_new_cell_is_new_files_and_one_entry(tmp_path, monkeypatch):
    """A cell made of a new configuration, traffic mix, check and metric
    reader, plus one workloads entry, runs end to end without an edit to
    any file that is already there."""
    data = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, data, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {p.relative_to(data): p.read_bytes()
              for p in data.rglob("*") if p.is_file()}
    conf = json.loads((data / "configs" / "yi-6b.json").read_text())
    (data / "configs" / "yi-6b-copy.json").write_text(json.dumps(conf))
    traffic = json.loads((data / "traffic" / "chat-decode.json").read_text())
    traffic.update(wave_jobs=4, batch_jobs=2, prompt_len=8,
                   decode_tokens=3, check_sequences=2)
    (data / "traffic" / "tiny-chat.json").write_text(json.dumps(traffic))
    (data / "checks" / "yi-6b-copy.tiny-chat.json").write_text(
        json.dumps({"limit": 1.0}))
    (data / "metrics" / "waves_per_s.py").write_text(
        "def read(run):\n    return run.waves / run.window_s\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "yi-6b-copy", "source": "x",
                             "file": "chipbench/configs/yi-6b-copy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "yi-6b-copy.tiny-chat",
                               "config": "yi-6b-copy",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "waves_per_s", "unit": "1/s",
                               "better": "higher",
                               "source": "host_clock", "layer": "queue",
                               "moves": "output_tokens_per_s",
                               "workloads": ["yi-6b-copy.tiny-chat"]})
    monkeypatch.setattr(spec, "HERE", data)
    out = harness.run_cell("yi-6b-copy.tiny-chat", 2**31 + 5, 0.2, True,
                           0.0, bench=bench, rehearse=True,
                           log=lambda _: None)
    assert out["correct"] and out["failed"] == 0
    assert out["metrics"]["waves_per_s"]["value"] > 0
    after = {p.relative_to(data): p.read_bytes()
             for p in data.rglob("*") if p.is_file() and
             p.relative_to(data) in before}
    assert after == before
