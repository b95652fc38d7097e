"""The command's contract: no chip, no result; the result line's keys;
the compared numbers last on both streams."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, run, spec

ROOT = spec.ROOT
CELL = "stablelm-1.6b.chat-decode"
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        harness.peaks("TPU v99")


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_result_line_and_limits(monkeypatch, capsys):
    line = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
            "device": {}, "limits": {"widest_logit_gap": [0.1, 0.5],
                                     "jobs_not_done": [0, 0]}}
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: line)
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) == 0
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert list(last) == LINE_KEYS + ["limits"]
    assert out.err.strip().splitlines()[-2:] == [
        "check widest_logit_gap: 0.1 (limit 0.5)",
        "check jobs_not_done: 0 (limit 0)"]


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_line_has_exactly_the_keys(trace):
    out = harness.run_cell(CELL, 2**31 + 11, 0.3, trace, 0.0,
                           rehearse=True, log=lambda _: None)
    assert list(out) == LINE_KEYS + ["limits"]     # no device plane here
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    kind = "per_layer" if trace else "end_to_end"
    reported = set(out["metrics"])
    assert reported <= set(spec.metric_names(spec.load_benchmark(), kind,
                                             CELL))
    if not trace:
        assert {"output_tokens_per_s", "latency_p50_ms", "latency_p95_ms",
                "setup_s"} == reported
    for name, (value, limit) in out["limits"].items():
        assert value <= limit, name


def test_federated_waves_report_runtime_imbalance(tmp_path, monkeypatch):
    """The zipf8 mix drives ``serve_jobs_federated`` over four runtimes
    (here all on the one host device), and the traced line reads the
    router's balance from the runtimes' item counts."""
    data = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, data, ignore=shutil.ignore_patterns(
        "__pycache__"))
    traffic = json.loads(
        (data / "traffic" / "chat-decode-zipf8.json").read_text())
    assert traffic["runtimes"] == 4
    traffic.update(wave_jobs=16, batch_jobs=2, prompt_len=8,
                   decode_tokens=3, check_sequences=4)
    (data / "traffic" / "tiny-zipf8.json").write_text(json.dumps(traffic))
    cell = "stablelm-1.6b.tiny-zipf8"
    (data / "checks" / f"{cell}.json").write_text(json.dumps({"limit": 0.1}))
    bench = json.loads(json.dumps(spec.load_benchmark()))
    bench["workloads"].append({"name": cell, "config": "stablelm-1.6b",
                               "traffic": "tiny-zipf8", "chips": 1,
                               "why": "x"})
    # the tiny cell reports what the four-chip cell reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "stablelm-1.6b.fed4-zipf8" in m.get("workloads", []):
            m["workloads"].append(cell)
    monkeypatch.setattr(spec, "HERE", data)
    for trace in (False, True):
        out = harness.run_cell(cell, 2**31 + 31, 0.2, trace, 0.0,
                               bench=bench, rehearse=True, log=lambda _: None)
        assert out["correct"] and out["attempted"] % 16 == 0
        if not trace:
            assert set(out["metrics"]) == {
                "output_tokens_per_s.fed4", "latency_p50_ms.fed4",
                "latency_p95_ms.fed4", "setup_s"}
    assert 1.0 <= out["metrics"]["fed_runtime_imbalance"]["value"] <= 4.0
    assert "queue_wait_p95_ms.fed4" in out["metrics"]
    assert not {"queue_wait_p95_ms", "sched_host_ms_per_chunk"} \
        & set(out["metrics"])


def test_checkout_without_the_program_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec.load_benchmark()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for extra in ([], ["--rehearse"]):
        proc = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload", CELL,
             "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
            cwd=tmp_path, env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode != 0 and proc.stdout == ""
