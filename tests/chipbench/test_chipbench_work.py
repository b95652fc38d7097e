"""chipbench/work.py against figures worked out by hand."""
import pytest

from chipbench import spec, work

STABLELM = spec.config("stablelm-1.6b").sizes
YI = spec.config("yi-6b").sizes
V5E_FLOPS, V5E_BW = 197e12, 819e9


@pytest.mark.parametrize("sizes,per_layer,weight_bytes", [
    # 2048*2048*2 (q, o) + 2048*2048*2 (k, v) + 3*2048*5632 (gated MLP);
    # 24 layers + the 2048 x 100352 head, 2 bytes each, + f32 LayerNorms
    (STABLELM, 51_380_224,
     (24 * 51_380_224 + 2048 * 100_352) * 2 + 2048 * 4 * 49 * 2),
    # 4096*4096*2 + 4096*512*2 + 3*4096*11008; 32 layers + 4096 x 64000
    # head, + f32 RMSNorm gains
    (YI, 173_015_040,
     (32 * 173_015_040 + 4096 * 64_000) * 2 + 4096 * 4 * 65),
])
def test_weight_bytes_per_decode_call(sizes, per_layer, weight_bytes):
    assert work.block_weights(sizes) == per_layer
    assert work.weight_bytes_per_call(sizes) == weight_bytes


def test_weights_per_call_are_2_88_and_11_6_gb():
    assert work.weight_bytes_per_call(STABLELM) / 1e9 == \
        pytest.approx(2.88, abs=0.005)
    assert work.weight_bytes_per_call(YI) / 1e9 == \
        pytest.approx(11.60, abs=0.005)


def test_decode_call_flops_and_bound():
    w = work.decode(STABLELM, rows=8, position=128)
    mm = 2 * 51_380_224 * 24 * 8
    attn = 2 * 2 * 32 * 64 * 129 * 8 * 24
    head = 2 * 2048 * 100_352 * 8
    assert w.flops == mm + attn + head == 23_221_239_808
    kv_per_position = 24 * 2 * 32 * 64 * 2
    assert w.bytes == (2_878_095_360            # weights and norms
                       + 8 * 2048 * 2           # embedding rows
                       + 8 * 129 * kv_per_position   # cache read
                       + 8 * kv_per_position    # new cache entries
                       + 8 * 100_352 * 2)       # logits
    assert w.bound(V5E_FLOPS, V5E_BW) == "bytes"
    # 3.08 GB at 819 GB/s: at least 3.77 ms per call
    assert w.least_seconds(V5E_FLOPS, V5E_BW) == pytest.approx(
        3_084_206_080 / V5E_BW)


def test_prefill_of_a_document_is_flop_bound():
    w = work.prefill(STABLELM, rows=16, prompt_len=1016)
    mm = 2 * 51_380_224 * 24 * 16 * 1016
    attn = 2 * 2 * 32 * 64 * (1016 * 1017 / 2) * 16 * 24
    head = 2 * 2048 * 100_352 * 16
    assert w.flops == pytest.approx(mm + attn + head)
    assert w.bound(V5E_FLOPS, V5E_BW) == "flops"
    assert w.least_seconds(V5E_FLOPS, V5E_BW) == pytest.approx(
        w.flops / V5E_FLOPS)


def test_chunk_is_one_prefill_and_decode_tokens_minus_one_calls():
    pre, dec = work.chunk(YI, rows=3, prompt_len=128, decode_tokens=4)
    assert pre == work.prefill(YI, 3, 128)
    assert dec == work.decode(YI, 3, 128) + work.decode(YI, 3, 129) \
        + work.decode(YI, 3, 130)
    assert work.chunk(YI, 0, 128, 4) == (work.ZERO, work.ZERO)
