"""Model-layer correctness: chunked attention vs O(s²) oracle (both causal
schedules), MoE dispatch vs dense loop oracle, SSD scan vs recurrence, and
prefill+decode == full forward for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced_config
from repro.models import model as M
from repro.models.attention import (chunked_attention, decode_attention,
                                    group_query_heads, reference_attention)

pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(7)


def qkv(b=2, sq=48, skv=48, g=2, m=2, hd=16, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, g, m, hd), dtype)
    k = jax.random.normal(ks[1], (b, skv, g, hd), dtype)
    v = jax.random.normal(ks[2], (b, skv, g, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("qc,kc", [(16, 16), (16, 32), (48, 48), (13, 7)])
def test_chunked_attention_matches_reference(qc, kc):
    q, k, v = qkv()
    out = chunked_attention(q, k, v, causal=True, q_chunk=qc, kv_chunk=kc)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_block_skip_schedule_identical():
    q, k, v = qkv(sq=64, skv=64)
    base = chunked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
    skip = chunked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16,
                             block_skip=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip),
                               rtol=2e-5, atol=2e-5)


def test_kv_len_masking():
    q, k, v = qkv(sq=8, skv=32)
    out = chunked_attention(q, k, v, causal=False, q_chunk=8, kv_chunk=8,
                            kv_len=jnp.array([20, 32]))
    ref = reference_attention(q, k, v, causal=False,
                              kv_len=jnp.array([20, 32]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("g,m", [(2, 2), (4, 1), (1, 4), (3, 2)])
def test_decode_attention_matches_reference(g, m):
    """Caches packed as (b, S, g * hd), per-row lengths."""
    q, k, v = qkv(sq=1, skv=40, g=g, m=m)
    kv_len = jnp.array([17, 40])
    b, s = k.shape[:2]
    out = decode_attention(q, k.reshape(b, s, -1), v.reshape(b, s, -1),
                           kv_len)
    ref = reference_attention(q, k, v, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# Run in a process of its own: the mesh needs 8 host devices, and every
# other test sees one.
_MESH_DECODE = r"""
import json, re, sys
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_reduced_config
from repro.launch.mesh import make_mesh_from
from repro.models import model as M
from repro.models import transformer as T
from repro.models.attention import decode_attention
from repro.sharding import ShardingRules, tree_shardings, use_mesh_rules

kv_heads = int(sys.argv[1])
mesh = make_mesh_from(jax.devices(), (2, 4), ("data", "model"))
rules = ShardingRules()
cfg = get_reduced_config("yi-6b").replace(dtype="float32", n_heads=8,
                                          n_kv_heads=kv_heads)
key = jax.random.PRNGKey(3)
params = M.init_params(cfg, key)
tokens = jax.random.randint(key, (4, 8), 0, cfg.vocab)
_, cache = M.prefill(cfg, params, tokens, max_len=16)
cache = dict(cache, pos=jnp.array([8, 3, 6, 1], jnp.int32))
tok = tokens[:, -1:]
want, want_cache = M.decode_step(cfg, params, cache, tok)
c_sh = tree_shardings(mesh, jax.eval_shape(lambda: cache), M.cache_axes(cfg),
                      rules)
p_sh = tree_shardings(mesh, jax.eval_shape(lambda: params),
                      M.param_axes(cfg), rules)
with use_mesh_rules(mesh, rules):
    got, got_cache = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t),
                             in_shardings=(p_sh, c_sh, None))(params, cache,
                                                              tok)
    # one layer's attention and cache write, operands sharded as the step
    # shards them
    heads = c_sh["k"].update(spec=c_sh["k"].spec[1:])
    sds = lambda shape, sh, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh)
    c = kv_heads * 16
    attend = jax.jit(decode_attention).lower(
        sds((4, 1, kv_heads, 8 // kv_heads, 16), heads),
        sds((4, 16, c), heads), sds((4, 16, c), heads),
        sds((4,), c_sh["pos"], jnp.int32)).compile().as_text()
    write = jax.jit(lambda *a: T.write_kv(cfg, *a)).lower(
        sds(cache["k"].shape, c_sh["k"]),
        sds((4, c), heads.update(spec=heads.spec[:1] + heads.spec[2:])),
        jnp.int32(1), sds((4,), c_sh["pos"], jnp.int32)).compile().as_text()
collective = (r"= \S+ (all-gather|all-reduce|reduce-scatter|all-to-all|"
              r"collective-permute)\(")
print(json.dumps({
    "cache_spec": [str(a) for a in c_sh["k"].spec],
    "logit_gap": float(jnp.abs(got - want).max()),
    "cache_gap": float(jnp.abs(got_cache["k"] - want_cache["k"]).max()),
    "collectives": re.findall(collective, attend + write),
    "ops": [len(re.findall(r" = ", t)) for t in (attend, write)]}))
"""


@pytest.mark.parametrize("kv_heads,spec", [
    (4, ["None", "data", "None", "model"]),   # one kv head per model shard
    (2, ["None", "data"])])                   # 2 heads over 4: replicated
def test_decode_under_tensor_parallel_mesh(kv_heads, spec):
    """On a (data 2, model 4) mesh the packed cache shards whole kv heads
    only, attention and the cache write run on each shard's own rows and
    heads with no collective, and the step gives the logits and cache of
    the unmeshed step."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", _MESH_DECODE, str(kv_heads)],
                         env=env, capture_output=True, text=True,
                         timeout=300, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["cache_spec"] == spec
    assert got["logit_gap"] < 1e-4 and got["cache_gap"] < 1e-4, got
    assert min(got["ops"]) > 0
    assert got["collectives"] == [], got


def test_moe_matches_dense_oracle_at_high_capacity():
    from repro.models import moe as moe_lib
    cfg = get_reduced_config("phi3.5-moe-42b-a6.6b").replace(dtype="float32")
    cfg = cfg.replace(moe=cfg.moe.__class__(
        num_experts=4, top_k=2, capacity_factor=8.0))  # no drops
    defs = moe_lib.moe_defs(cfg)
    from repro.models.layers import init_from_defs
    p = init_from_defs(defs, KEY)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32) * 0.3
    out, aux = moe_lib.moe_fwd(cfg, p, x)
    ref, aux_ref = moe_lib.moe_fwd_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert float(aux) == pytest.approx(float(aux_ref), rel=1e-4)


def test_moe_local_dispatch_matches_oracle_at_high_capacity():
    """The dispatch_groups>1 perf path must agree with the dense oracle when
    capacity is unconstrained (no drops in any group)."""
    from repro.models import moe as moe_lib
    cfg = get_reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    cfg = cfg.replace(moe=cfg.moe.__class__(
        num_experts=4, top_k=2, capacity_factor=8.0, dispatch_groups=4))
    from repro.models.layers import init_from_defs
    p = init_from_defs(moe_lib.moe_defs(cfg), KEY)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32) * 0.3
    out, aux = moe_lib.moe_fwd(cfg, p, x)
    ref, _ = moe_lib.moe_fwd_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_decode_unroll_matches_scan_decode():
    cfg = get_reduced_config("yi-6b").replace(dtype="float32")
    params = M.init_params(cfg, KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    _, cache = M.prefill(cfg, params, tokens, max_len=32)
    tok = jnp.ones((2, 1), jnp.int32)
    lg_scan, c_scan = M.decode_step(cfg, params, cache, tok)
    cfg_u = cfg.replace(decode_unroll=True)
    lg_unroll, c_unroll = M.decode_step(cfg_u, params, cache, tok)
    np.testing.assert_allclose(np.asarray(lg_scan), np.asarray(lg_unroll),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_scan["k"]),
                               np.asarray(c_unroll["k"]), rtol=1e-5,
                               atol=1e-5)


# stablelm-1.6b's shape of attention (one query head per kv head) and
# yi-6b's grouped queries, through the layer loop and unrolled
@pytest.mark.parametrize("arch,kv_heads,unroll", [
    ("stablelm-1.6b", 4, False), ("stablelm-1.6b", 4, True),
    ("yi-6b", None, False), ("yi-6b", None, True)])
def test_ragged_decode_matches_forward(arch, kv_heads, unroll):
    """Rows at different positions each write and read their own: row r
    decodes from position ``s - back[r]``, which is the forward pass over
    its first ``s - back[r]`` tokens followed by the decoded ones."""
    cfg = get_reduced_config(arch).replace(dtype="float32",
                                           decode_unroll=unroll)
    if kv_heads:
        cfg = cfg.replace(n_kv_heads=kv_heads)
    params = M.init_params(cfg, KEY)
    s, steps, back = 12, 3, np.array([0, 5, 2])
    tokens = jax.random.randint(KEY, (len(back), s + steps), 0, cfg.vocab)
    _, cache = M.prefill(cfg, params, tokens[:, :s], max_len=32)
    assert cache["k"].shape == (cfg.n_layers, len(back), 32,
                                cfg.n_kv_heads * cfg.resolved_head_dim)
    cache = dict(cache, pos=jnp.asarray(s - back, jnp.int32))
    got = []
    for t in range(steps):
        rows = [tokens[r, s - back[r] + t] for r in range(len(back))]
        lg, cache = M.decode_step(cfg, params, cache,
                                  jnp.stack(rows)[:, None])
        got.append(np.asarray(lg[:, 0]))
    np.testing.assert_array_equal(np.asarray(cache["pos"]), s - back + steps)
    for r in range(len(back)):
        n = s - back[r] + steps
        want, _ = M.forward(cfg, params, tokens[r:r + 1, :n])
        for t in range(steps):
            np.testing.assert_allclose(got[t][r],
                                       np.asarray(want[0, n - steps + t]),
                                       rtol=2e-4, atol=2e-4)


def _served_and_stepped(cfg, prompt_len, decode_tokens):
    """One call of the engine's decode program on a prefilled cache, and
    an undonated step-by-step greedy decode of the same cache: (the
    given cache, the program's tokens and cache, the steps' tokens and
    cache)."""
    from repro.core.types import DeviceKind
    from repro.serve.engine import HeteroServeEngine
    from repro.train.trainer import GroupDef

    eng = HeteroServeEngine(cfg, [GroupDef("accel", DeviceKind.ACCEL)],
                            prompt_len=prompt_len,
                            decode_tokens=decode_tokens)
    prefill_fn, decode_fn = eng._fns_for(2)
    step = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t))
    tokens = np.stack([eng._prompt(i) for i in range(2)])
    logits, cache = prefill_fn(eng.params, tokens, None)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    kept = jax.tree.map(jnp.copy, cache)
    got, got_cache = decode_fn(eng.params, cache, tok)
    want = []
    for _ in range(decode_tokens - 1):
        logits, kept = step(eng.params, kept, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        want.append(tok)
    return (cache, np.asarray(got), got_cache,
            np.asarray(jnp.concatenate(want, axis=1)), kept)


@pytest.mark.parametrize("unroll", [False, True])
def test_engine_decode_donates_cache(unroll):
    """The engine's decode program runs a chunk's whole greedy decode in
    one call that takes its cache by donation, and gives the tokens and
    the cache an undonated step-by-step decode gives (layer loop scanned
    or unrolled)."""
    cfg = get_reduced_config("stablelm-1.6b").replace(decode_unroll=unroll)
    given, got, cache, want, kept = _served_and_stepped(cfg, 8, 4)
    assert all(a.is_deleted() for a in jax.tree.leaves(given))
    assert got.shape == (2, 3) and got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    for name in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(cache[name]),
                                      np.asarray(kept[name]))


def test_engine_decode_donates_hybrid_cache():
    """The hybrid layer pattern's decode program takes its whole cache
    (SSM state, conv windows, keys and values) by donation, and serves
    the tokens and leaves the cache an undonated step-by-step decode
    serves and leaves."""
    cfg = get_reduced_config("granite-4.0-h-micro")
    given, got, cache, want, kept = _served_and_stepped(cfg, 11, 4)
    assert all(a.is_deleted() for a in jax.tree.leaves(given))
    assert got.shape == (2, 3) and got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    for name in ("ssm_state", "conv", "k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(cache[name]),
                                      np.asarray(kept[name]))


def test_engine_serves_the_first_token_alone_without_a_decode_program(
        monkeypatch):
    """With one token to generate, a chunk is its prefill and the argmax
    of its logits: no decode program is built or called."""
    from repro.core.types import DeviceKind
    from repro.queue import Job
    from repro.serve.engine import HeteroServeEngine
    from repro.train.trainer import GroupDef

    def no_decode(*args):
        raise AssertionError("a decode program was built")

    monkeypatch.setattr(M, "decode_loop", no_decode)
    cfg = get_reduced_config("stablelm-1.6b")
    eng = HeteroServeEngine(cfg, [GroupDef("accel", DeviceKind.ACCEL)],
                            prompt_len=8, decode_tokens=1)
    rep = eng.serve_jobs([Job(items=2) for _ in range(2)], batch_jobs=2,
                         timeout_s=120.0)
    assert rep.drained and rep.done == 2
    sample = np.asarray(rep.outputs["accel"]["sample"]["tokens"])
    rows = rep.outputs["accel"]["sample"]["rows"]
    assert sample.shape[1] == 1
    prompts = np.zeros((sample.shape[0], 8), np.int32)
    prompts[:len(rows)] = np.stack([eng._prompt(i) for i in rows])
    prefill_fn, _ = eng._fns_for(sample.shape[0])
    logits, _ = prefill_fn(eng.params, prompts, None)
    np.testing.assert_array_equal(
        sample[:len(rows), 0], np.argmax(logits[:len(rows), -1], -1))


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "granite-4.0-h-micro"])
def test_hybrid_keys_and_values_share_the_transformer_layout(arch):
    """Both hybrid layouts keep attention keys and values as the
    transformer does: (layers, batch, positions, kv heads * head_dim)."""
    cfg = get_reduced_config(arch)
    kv = cfg.n_kv_heads * cfg.resolved_head_dim
    params = M.init_params(cfg, KEY)
    _, cache = M.prefill(cfg, params, jnp.zeros((3, 9), jnp.int32),
                         max_len=16)
    abstract = M.init_cache(cfg, 3, 16, abstract=True)
    keys = [k for k in cache if k in ("k", "v", "ak", "av")]
    assert len(keys) == 2
    for k in keys:
        assert cache[k].ndim == 4 and cache[k].shape[1:] == (3, 16, kv)
        assert abstract[k].shape == cache[k].shape
        assert len(M.cache_axes(cfg)[k]) == 4


def test_ssd_scan_matches_recurrence():
    from repro.models.ssm import ssd_scan
    from repro.kernels.ref import ssd_scan_ref
    b, s, nh, hd, n = 2, 40, 3, 8, 6
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, 1, n)) * 0.5
    y, _ = ssd_scan(x, dt, A, B, C, chunk=16)
    # oracle layout: (BH, S, ...) with heads flattened
    xf = x.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)
    dtf = dt.transpose(0, 2, 1).reshape(b * nh, s)
    Af = jnp.tile(A, b)
    Bf = jnp.repeat(B, nh, 2).transpose(0, 2, 1, 3).reshape(b * nh, s, n)
    Cf = jnp.repeat(C, nh, 2).transpose(0, 2, 1, 3).reshape(b * nh, s, n)
    ref = ssd_scan_ref(xf, dtf, Af, Bf, Cf) \
        .reshape(b, nh, s, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-7b", "phi3-medium-14b",
                                  "stablelm-1.6b", "musicgen-large",
                                  "phi3.5-moe-42b-a6.6b",
                                  "granite-moe-1b-a400m", "xlstm-350m",
                                  "zamba2-1.2b", "phi-3-vision-4.2b",
                                  "granite-4.0-h-micro"])
def test_prefill_decode_matches_forward(arch):
    cfg = get_reduced_config(arch).replace(dtype="float32")
    params = M.init_params(cfg, KEY)
    B, S = 2, 32
    s_text = S - cfg.prefix_len
    tokens = jax.random.randint(KEY, (B, s_text), 0, cfg.vocab)
    prefix = (jax.random.normal(KEY, (B, cfg.prefix_len, cfg.d_model),
                                jnp.float32) * 0.1
              if cfg.prefix_len else None)
    logits_full, _ = M.forward(cfg, params, tokens, prefix)
    lg_pre, cache = M.prefill(cfg, params, tokens[:, :-1], prefix,
                              max_len=64)
    a = np.asarray(lg_pre[:, -1], np.float32)
    b_ = np.asarray(logits_full[:, -2], np.float32)
    assert np.abs(a - b_).max() / (np.abs(b_).max() + 1e-9) < 2e-3
    lg_dec, _ = M.decode_step(cfg, params, cache, tokens[:, -1:])
    c = np.asarray(lg_dec[:, 0], np.float32)
    d = np.asarray(logits_full[:, -1], np.float32)
    assert np.abs(c - d).max() / (np.abs(d).max() + 1e-9) < 2e-3


@pytest.mark.parametrize("causal", [True, False])
def test_flash_vjp_grads(causal):
    from repro.models.attention import flash_attention_jax
    b, s, g, m, hd, qc, kc = 2, 64, 2, 2, 16, 16, 16
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, s, g, m, hd))
    k = jax.random.normal(ks[1], (b, s, g, hd))
    v = jax.random.normal(ks[2], (b, s, g, hd))
    do = jax.random.normal(ks[3], (b, s, g, m, hd))
    f = lambda q, k, v: (flash_attention_jax(q, k, v, causal, qc, kc)
                         * do).sum()
    r = lambda q, k, v: (reference_attention(q, k, v, causal=causal)
                         * do).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_ce_matches_plain():
    from repro.train.loss import chunked_cross_entropy, cross_entropy
    b, s, d, v = 2, 24, 16, 64
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (b, s, d))
    w = jax.random.normal(ks[1], (d, v)) * 0.3
    labels = jax.random.randint(ks[2], (b, s), 0, v)
    loss_c, m_c = chunked_cross_entropy(x, w, labels, chunk=7)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    loss_p, m_p = cross_entropy(logits, labels)
    assert float(loss_c) == pytest.approx(float(loss_p), rel=1e-5)
    # gradients too (the remat'd backward)
    g_c = jax.grad(lambda xx: chunked_cross_entropy(xx, w, labels,
                                                    chunk=7)[0])(x)
    g_p = jax.grad(lambda xx: cross_entropy(
        jnp.einsum("bsd,dv->bsv", xx, w), labels)[0])(x)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_p),
                               rtol=1e-4, atol=1e-5)
