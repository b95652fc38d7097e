"""Compile the main path and the Pallas kernels for one described TPU v5e
chip, at real widths, without a chip.

The TPU compiler refuses here what interpret mode never sees: block shapes
off the (8, 128) tiling, primitives Mosaic cannot lower, programs that do
not fit the chip's 16 GB. Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.kernels.ops import attention_bshd, decode_attention_bshd, ssd_bshn
from repro.models import model as M
from repro.train.trainer import bucket

V5E_HBM_BYTES = 16 * 10**9
# the serve shapes chip_smoke.py drives: stablelm-1.6b, 128-token prompts,
# 32 decoded tokens, chunks padded to power-of-two batches of up to
# 8 jobs x 2 requests
PROMPT_LEN, DECODE_TOKENS = 128, 32
BATCHES = (1, 2, 4, 8, 16)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used
    return used


@pytest.mark.parametrize("batch", BATCHES)
def test_stablelm_serve_step_compiles_for_v5e(one_chip, batch):
    cfg = get_config("stablelm-1.6b")
    max_len = bucket(PROMPT_LEN + DECODE_TOKENS)
    params = _on(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    prompts = _on(one_chip,
                  jax.ShapeDtypeStruct((batch, PROMPT_LEN), jnp.int32))

    def prefill(p, t):
        return M.prefill(cfg, p, t, None, max_len=max_len)

    def decode(p, c, t):
        return M.decode_step(cfg, p, c, t)

    pre = jax.jit(prefill).lower(params, prompts).compile()
    assert _fits(pre) > 3 * 10**9          # 1.6B bf16 params are resident
    cache = _on(one_chip, jax.eval_shape(prefill, params, prompts)[1])
    tok = _on(one_chip, jax.ShapeDtypeStruct((batch, 1), jnp.int32))
    _fits(jax.jit(decode).lower(params, cache, tok).compile())


#: bytes per element of the HLO element types these programs hold
HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
             "u32": 4, "f32": 4}


def _unfused_ops(hlo: str):
    """The fused computations' names, and (computation, opcode, element
    count, bytes, shape) of each array-valued op that no fusion holds."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        op = re.match(r"\s+(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* "
                      r"([\w\-]+)\(", line)
        if op and comp not in fused:
            shape = tuple(int(d) for d in op.group(2).split(",") if d)
            n = math.prod(shape)
            out.append((comp, op.group(3), n,
                        n * HLO_BYTES.get(op.group(1), 4), shape))
    return fused, out


#: prompt and answer lengths of each model's cell: chat for the dense
#: models, 2k documents for the hybrid
CELL_LENGTHS = {"stablelm-1.6b": (128, 64), "yi-6b": (128, 64),
                "granite-4.0-h-micro": (1984, 64)}


def _cell_decode(one_chip, arch):
    """The model, its params, an 8-row cache of the cell's bucket and the
    token a decode reads, as v5e arguments."""
    cfg = get_config(arch)
    batch, max_len = 8, bucket(sum(CELL_LENGTHS[arch]))
    params = _on(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on(one_chip, M.init_cache(cfg, batch, max_len, abstract=True))
    tok = _on(one_chip, jax.ShapeDtypeStruct((batch, 1), jnp.int32))
    return cfg, params, cache, tok


#: the most a decode loop may copy of its cache as the program starts or
#: ends, as a share of the cache's bytes. granite's loop carries its conv
#: windows in another layout than the argument's and copies them in and
#: out (2 x 7.5 MB, 2 % of its cache); a copy of any key, value or state
#: leaf is 9 % of a cache or more.
ENTRY_CACHE_COPY_SHARE = 0.025


def _assert_in_place(compiled, params, cache, loop: bool = False) -> None:
    """The donated cache is written in place: no copy of one layer's
    slice of any stacked leaf or more, the whole cache aliased to the
    output, and temporaries a small share of it.

    ``loop``: the program is a loop of decode steps, which must stay one
    while loop at the program's entry, its body checked. The compiler may carry a weight
    stack through it in the layout the products read and relay the stack
    once as the program starts: a copy at the program's entry shaped as a
    params leaf is allowed, and the temporaries may hold it. Copies of
    cache leaves there may hold ENTRY_CACHE_COPY_SHARE of the cache's
    bytes in all; no other copy of a layer's slice may run."""
    stacked = {n: a for n, a in cache.items() if n != "pos"}
    # the smallest slice of one layer of any stacked leaf
    layer = min(a.size // a.shape[0] for a in stacked.values())
    hlo = compiled.as_text()
    entry = re.search(r"^ENTRY %([\w.\-]+) ", hlo, re.M)
    fused, ops = _unfused_ops(hlo)
    # the parser read this text: fused computations and the fusions
    # that call them
    assert fused and any(o[1] == "fusion" for o in ops)
    at_entry = [o for o in ops if loop and o[0] == entry.group(1)
                and o[1] == "copy"]
    weights = {a.shape for a in jax.tree.leaves(params)}
    relaid = [o for o in at_entry if o[4] in weights]
    leaves = {a.shape for a in stacked.values()}
    cache_copies = [o for o in at_entry if o[4] in leaves]
    big = [o for o in ops if o[1] == "copy" and o[2] >= layer
           and o not in relaid + cache_copies]
    assert not big, big
    cache_bytes = sum(a.size * a.dtype.itemsize for a in stacked.values())
    copied = sum(o[3] for o in cache_copies)
    assert copied <= ENTRY_CACHE_COPY_SHARE * cache_bytes, cache_copies
    if loop:
        # one loop of steps at the entry, its body among the checked
        bodies = re.findall(r" while\(.*?body=%([\w.\-]+)",
                            hlo[entry.start():])
        assert len(bodies) == 1 and any(o[0] == bodies[0] for o in ops)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= cache_bytes
    assert ma.temp_size_in_bytes < 0.05 * cache_bytes + copied + sum(
        o[3] for o in relaid), ma.temp_size_in_bytes


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "yi-6b",
                                  "granite-4.0-h-micro"])
def test_decode_updates_cache_in_place_on_v5e(one_chip, arch):
    """One decode step (cache donated) on the cells' shapes: the donated
    cache is written in place, and no layer's slice of it is copied, as
    happens when the stored layout and the one the layer reads and writes
    differ (head_dim 64 below the 128 lanes). For the hybrid the cache is
    keys and values beside each Mamba-2 layer's state and conv window."""
    cfg, params, cache, tok = _cell_decode(one_chip, arch)
    compiled = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t),
                       donate_argnums=(1,)).lower(params, cache,
                                                  tok).compile()
    _assert_in_place(compiled, params, cache)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "yi-6b",
                                  "granite-4.0-h-micro"])
def test_decode_loop_updates_cache_in_place_on_v5e(one_chip, arch):
    """The engine's decode program, a chunk's 63 greedy steps in one loop
    with the cache donated, on the cells' shapes: the cache rides in the
    loop's carry and each step writes it in place; no step copies the
    carried cache or a layer's slice of it, and the program copies at
    most granite's conv windows of its cache once per call."""
    cfg, params, cache, tok = _cell_decode(one_chip, arch)
    steps = CELL_LENGTHS[arch][1] - 1
    compiled = jax.jit(lambda p, c, t: M.decode_loop(cfg, p, c, t, steps),
                       donate_argnums=(1,)).lower(params, cache,
                                                  tok).compile()
    _assert_in_place(compiled, params, cache, loop=True)


def test_hybrid_prefill_of_the_largest_bucket_fits_v5e(one_chip):
    """granite-4.0-h-micro's prefill of 16 rows of 2k documents, the
    largest batch its cell warms up: the chunked SSD holds one chunk's
    (256, 256) tiles at a time, so weights, temporaries and the cache fit
    the chip."""
    cfg = get_config("granite-4.0-h-micro")
    prompt, answer = CELL_LENGTHS[cfg.arch_id]
    params = _on(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    prompts = _on(one_chip, jax.ShapeDtypeStruct((16, prompt), jnp.int32))
    compiled = jax.jit(lambda p, t: M.prefill(
        cfg, p, t, None, max_len=bucket(prompt + answer))).lower(
            params, prompts).compile()
    used = _fits(compiled) + compiled.memory_analysis().output_size_in_bytes
    assert used < 0.85 * V5E_HBM_BYTES, used


def test_flash_attention_compiles_for_v5e(one_chip):
    b, s, h, d = 8, PROMPT_LEN, 32, 64
    x = _on(one_chip, jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16))
    compiled = attention_bshd.lower(x, x, x, n_heads=h,
                                    n_kv_heads=h).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_for_v5e(one_chip):
    b, h, d = 8, 32, 64
    S = bucket(PROMPT_LEN + DECODE_TOKENS)
    q = _on(one_chip, jax.ShapeDtypeStruct((b, 1, h, d), jnp.bfloat16))
    kv = _on(one_chip, jax.ShapeDtypeStruct((b, S, h, d), jnp.bfloat16))
    kv_len = _on(one_chip, jax.ShapeDtypeStruct((b,), jnp.int32))
    compiled = decode_attention_bshd.lower(q, kv, kv, kv_len, n_heads=h,
                                           n_kv_heads=h).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_for_v5e(one_chip):
    ssm = get_config("zamba2-1.2b").ssm
    b, s = 1, 4 * ssm.chunk_size
    nh = ssm.expand * get_config("zamba2-1.2b").d_model // ssm.head_dim
    f32 = jnp.float32
    x = _on(one_chip, jax.ShapeDtypeStruct((b, s, nh, ssm.head_dim), f32))
    dt = _on(one_chip, jax.ShapeDtypeStruct((b, s, nh), f32))
    A = _on(one_chip, jax.ShapeDtypeStruct((nh,), f32))
    BC = _on(one_chip, jax.ShapeDtypeStruct(
        (b, s, ssm.n_groups, ssm.d_state), f32))
    compiled = ssd_bshn.lower(x, dt, A, BC, BC,
                              chunk=ssm.chunk_size).compile()
    assert "tpu_custom_call" in compiled.as_text()
