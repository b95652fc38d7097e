"""The serve entry point driven in-process: ``main(argv)`` returns the
report it prints, exits nonzero when its single runtime loses a device
group, and reports what each group's chunks produced; federated runtimes
bind to the device they are given; the compile cache goes where the
environment says."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced_config
from repro.core.types import DeviceKind
from repro.launch import compile_cache
from repro.launch import serve as serve_mod
from repro.models import model as M
from repro.queue import Job
from repro.serve.engine import HeteroServeEngine
from repro.train.trainer import GroupDef

ARGV = ["--arch", "stablelm-1.6b", "--reduced", "--queue", "--requests",
        "16", "--job-items", "2", "--prompt-len", "8", "--decode-tokens",
        "4", "--tenants", "gold:weight=4,free:weight=1"]


def _direct_greedy(eng, rows, batch):
    """Greedy prefill+decode of ``rows``' prompts padded to ``batch``,
    outside the scheduler."""
    cfg = eng.cfg
    prompts = np.zeros((batch, eng.prompt_len), np.int32)
    prompts[:len(rows)] = np.stack([eng._prompt(i) for i in rows])
    logits, cache = M.prefill(cfg, eng.params, jnp.asarray(prompts), None,
                              max_len=eng.max_len)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    toks = [tok]
    for _ in range(eng.decode_tokens - 1):
        logits, cache = M.decode_step(cfg, eng.params, cache, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        toks.append(tok)
    return np.asarray(jnp.concatenate(toks, axis=1))


def test_main_returns_the_report_it_prints(capsys):
    out = serve_mod.main(ARGV)
    printed = json.loads(capsys.readouterr().out)
    assert printed == json.loads(json.dumps(out))
    assert out["done"] == out["jobs"] == 8
    assert out["drained"] is True and out["dead_groups"] == []
    vocab = get_reduced_config("stablelm-1.6b").vocab
    assert out["outputs"]
    for o in out["outputs"].values():
        assert o["devices"] == ["cpu:0"] and o["chunks"] >= 1
        lo, hi = o["token_range"]
        assert 0 <= lo <= hi < vocab
        tokens = np.asarray(o["sample"]["tokens"])
        assert tokens.shape[1] == 4
        assert 1 <= len(o["sample"]["rows"]) <= tokens.shape[0]


def test_main_exits_nonzero_when_a_group_dies(monkeypatch, capsys):
    def groups(spec):
        return [GroupDef("accel", DeviceKind.ACCEL, fixed_chunk=4),
                GroupDef("cpu0", DeviceKind.BIG, fail_after_chunks=0)]
    monkeypatch.setattr(serve_mod, "parse_groups", groups)
    with pytest.raises(SystemExit) as exc:
        serve_mod.main(ARGV)
    assert exc.value.code not in (0, None)
    assert "cpu0" in str(exc.value.code)
    # the report still went out, and shows the survivors finished the work
    printed = json.loads(capsys.readouterr().out)
    assert printed["dead_groups"] == ["cpu0"]
    assert printed["done"] == printed["jobs"]


@pytest.fixture(scope="module")
def engine():
    cfg = get_reduced_config("stablelm-1.6b").replace(dtype="float32")
    groups = [GroupDef("accel", DeviceKind.ACCEL, fixed_chunk=4),
              GroupDef("cpu0", DeviceKind.BIG)]
    return HeteroServeEngine(cfg, groups, prompt_len=8, decode_tokens=3)


def test_sampled_chunks_equal_direct_greedy_decode(engine):
    rep = engine.serve_jobs([Job(items=2) for _ in range(8)], batch_jobs=4,
                            timeout_s=120.0)
    assert rep.done == 8 and rep.outputs
    for g, o in rep.outputs.items():
        got = np.asarray(o["sample"]["tokens"])
        want = _direct_greedy(engine, o["sample"]["rows"], got.shape[0])
        np.testing.assert_array_equal(got, want, err_msg=g)


def test_federated_runtime_binds_executors_and_params_to_device(engine):
    cpu = jax.devices("cpu")[0]
    ex = engine._executor_for(engine.groups[0], "r9/", device=cpu)
    assert ex.device is cpu
    # the params already live on the only CPU device: binding copies none
    assert engine._params_on(cpu) is engine.params
    assert engine._params_on(None) is engine.params
    # with one device the federated path binds nothing
    frep = engine.serve_jobs_federated([Job(items=2) for _ in range(4)],
                                       runtimes=2, timeout_s=120.0)
    assert frep.drained and frep.fed.done == 4
    assert all(engine._executors[k].device is None
               for k in engine._executors if k.startswith(("r0/", "r1/")))
    assert all(o["devices"] == ["cpu:0"] for o in frep.outputs.values())


def test_compile_cache_dir_follows_environment(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
