"""Infrastructure tests: checkpoint, data pipeline, sharding rules, runtime
(watchdog / straggler / elastic)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.core import (Chunk, ChunkRecord, DeviceKind, GroupSpec,
                        ThroughputTracker, Token)
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLMData
from repro.runtime import StragglerDetector, Watchdog
from repro.sharding.rules import Packed, ShardingRules
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path, keep_n=2)
    tree = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                       "blocks": (np.ones(2), np.zeros(3))},
            "step": np.int32(7)}
    ck.save(7, tree, meta={"loss": 1.5})
    out, meta = ck.restore()
    assert meta["step"] == 7 and meta["loss"] == 1.5
    np.testing.assert_array_equal(out["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(out["params"]["blocks"][0], np.ones(2))


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(tmp_path, keep_n=2)
    for s in (1, 2, 3, 4):
        ck.save(s, {"x": np.ones(1) * s})
    assert ck.steps() == [3, 4]
    assert ck.latest_step() == 4
    out, _ = ck.restore(3)
    assert out["x"][0] == 3.0


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save_async(1, {"x": np.ones(4)})
    ck.wait()
    assert ck.latest_step() == 1


def test_checkpoint_jax_arrays(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(2, {"w": jnp.ones((3, 3), jnp.bfloat16)})
    out, _ = ck.restore()
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["w"], np.float32),
                                  np.ones((3, 3), np.float32))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_idempotent():
    d = SyntheticLMData(DataConfig(seq_len=16, vocab=100, seed=3))
    b1 = d.batch(10, 14)
    b2 = d.batch(10, 14)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # chunk identity: any group materializes the same range identically
    sub = d.batch(12, 14)
    np.testing.assert_array_equal(b1["tokens"][2:], sub["tokens"])


def test_data_padding_masked():
    d = SyntheticLMData(DataConfig(seq_len=8, vocab=50, seed=0))
    b = d.batch(0, 3, pad_to=8)
    assert b["tokens"].shape == (8, 8)
    assert b["loss_mask"][:3].all() and not b["loss_mask"][3:].any()


def test_prefetcher_double_buffers():
    calls = []

    def make(i):
        calls.append(i)
        return {"i": i}

    pf = Prefetcher(make, depth=2)
    got = [pf.next()["i"] for _ in range(5)]
    pf.stop()
    assert got == [0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.zeros(shape)


def test_rules_basic_mapping():
    r = ShardingRules()
    mesh = FakeMesh((16, 16), ("data", "model"))
    spec = r.spec(mesh, ("vocab", "embed"), (64000, 4096))
    assert spec == P("model", "data")


def test_rules_divisibility_fallback():
    r = ShardingRules()
    mesh = FakeMesh((16, 16), ("data", "model"))
    # 40 heads % 16 != 0 -> head axis replicated
    spec = r.spec(mesh, ("embed", "heads", "head_dim"), (5120, 40, 128))
    assert spec == P("data")


def test_rules_multi_axis_prefix_fallback():
    r = ShardingRules()
    mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    # batch 32 divisible by pod·data=32 -> both axes used
    assert r.spec(mesh, ("act_batch", None), (32, 7)) == P(("pod", "data"))
    # batch 2 only divisible by pod -> prefix fallback
    assert r.spec(mesh, ("act_batch", None), (2, 7)) == P("pod")
    # batch 1 -> replicated
    assert r.spec(mesh, ("act_batch", None), (1, 7)) == P()


def test_rules_no_axis_reuse():
    r = ShardingRules()
    mesh = FakeMesh((16, 16), ("data", "model"))
    # both dims map to model -> second falls back (no double use)
    spec = r.spec(mesh, ("vocab", "mlp"), (1600, 1600))
    assert spec == P("model")


def test_long_context_overrides():
    r = ShardingRules().for_shape_kind("long_decode")
    mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    spec = r.spec(mesh, ("cache_batch", "cache_seq", "cache_kv_heads", None),
                  (1, 524288, 32, 64))
    assert spec == P(None, ("pod", "data"), "model")


@pytest.mark.parametrize("blocks,spec", [(32, P("data", None, "model")),
                                         (4, P("data"))])
def test_rules_packed_axis_shards_whole_blocks(blocks, spec):
    r = ShardingRules()
    mesh = FakeMesh((16, 16), ("data", "model"))
    # a (batch, seq, kv_heads * 128) cache: 32 heads split 2 per shard;
    # 4 heads do not split over 16, though their 512 lanes would
    axes = ("cache_batch", "cache_seq", Packed("cache_kv_heads", blocks))
    assert r.spec(mesh, axes, (128, 32768, blocks * 128)) == spec


# ---------------------------------------------------------------------------
# runtime: watchdog + straggler
# ---------------------------------------------------------------------------

def _rec(group, size, t0, t1):
    return ChunkRecord(Token(Chunk(0, size), group, DeviceKind.BIG),
                       tg1=t0, tg5=t1, tc1=t0, tc2=t0, tc3=t1)


def test_watchdog_flags_hung_group(vclock):
    tr = ThroughputTracker()
    tr.seed("g", 1000.0)
    dead = []
    wd = Watchdog(tr, timeout_factor=1.0, min_timeout_s=0.05,
                  on_dead=dead.append, clock=vclock.now)
    wd.chunk_started("g", expected_items=10)   # expected 0.01s
    vclock.advance(0.12)
    assert wd.check() == ["g"]
    assert dead == ["g"]
    assert wd.check() == []                    # only reported once


def test_watchdog_heartbeat_clears(vclock):
    tr = ThroughputTracker()
    tr.seed("g", 1000.0)
    wd = Watchdog(tr, timeout_factor=1.0, min_timeout_s=0.05,
                  clock=vclock.now)
    wd.chunk_started("g", 10)
    wd.chunk_finished("g")
    vclock.advance(0.12)
    assert wd.check() == []


def test_straggler_detector_normalizes_by_own_baseline():
    tr = ThroughputTracker(alpha=1.0)
    det = StragglerDetector(tr, threshold=0.5, warmup_chunks=1)
    # healthy: λ=100 for "fast", λ=10 for "slow-but-steady"
    for t in range(3):
        tr.update(_rec("fast", 100, t, t + 1.0))
        tr.update(_rec("steady", 10, t, t + 1.0))
    assert det.observe() == []
    # fast degrades to 30 (<50% of its own 100 baseline)
    tr.update(_rec("fast", 30, 10, 11.0))
    reports = det.observe()
    assert [r.group for r in reports] == ["fast"]
    assert reports[0].slowdown < 0.5
