"""The hybrid family (granite-4.0-h): its weights against the program's
tree, its plain float32 reference against the program's prefill and cached
decode, and its work counts against a count by hand.

The small model keeps what the published one has: two periods of
[M, M, A, M], d_model 128, 4 query / 2 kv heads, d_state 16, an SSD chunk
of 8 under a 21-token prompt (a padded last chunk), the four multipliers.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, spec, weights
from chipbench.families import hybrid as H
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.models import model as M

PROMPT, DECODE, SEED = 21, 6, 2**31 + 161
#: float32 program against float32 reference: the same values, summed in
#: another order, and the SSD by chunks against its quadratic form (exp of
#: differences of running sums); measured 1.9e-6 on logits of unit size,
#: and a state rounded to bfloat16 between decode steps reads 1.2e-4
TOL = 2e-5


def _small_config():
    cfg = reduced(get_config("granite-4.0-h-micro"))
    return cfg.replace(d_model=128, head_dim=32, ssm=dataclasses.replace(
        cfg.ssm, d_state=16, chunk_size=8))


@pytest.fixture(scope="module")
def small():
    cfg = _small_config()
    s = H.sizes_of(cfg)
    prompts = np.random.default_rng(5).integers(
        0, s.vocab, (3, PROMPT)).astype(np.int32)
    return cfg, s, prompts


def _served(cfg, params, prompts, decode_step=None):
    """Greedy prefill + cached decode: the served tokens and the logits
    each was chosen from."""
    decode_step = jax.jit(partial(decode_step or M.decode_step, cfg))
    logits, cache = jax.jit(partial(M.prefill, cfg, max_len=32))(
        params, jnp.asarray(prompts))
    seen, toks = [], []
    for i in range(DECODE):
        if i:
            logits, cache = decode_step(params, cache, tok)
        seen.append(np.asarray(logits[:, -1], np.float32))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        toks.append(tok)
    return np.asarray(jnp.concatenate(toks, 1)), np.stack(seen, 1)


def _float32(s):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        weights.make_params(s, SEED))


def _gap_to_reference(cfg, s, prompts, params, decode_step=None):
    served, seen = _served(cfg.replace(dtype="float32"), params, prompts,
                           decode_step)
    want = H.Reference(s, SEED).logits(
        np.concatenate([prompts, served[:, :-1]], 1), PROMPT - 1)
    return np.abs(seen - want).max()


def test_small_model_keeps_the_published_shape(small):
    cfg, s, _ = small
    assert s.layer_types == ("mamba", "mamba", "attention", "mamba") * 2
    assert (s.n_mamba, s.n_attn, s.n_layers) == (6, 2, 8)
    assert PROMPT % s.ssm_chunk and s.n_heads > s.n_kv_heads
    full = spec.config("granite-4.0-h-micro").sizes
    assert full == H.sizes_of(get_config("granite-4.0-h-micro"))
    assert (full.n_mamba, full.n_attn, full.ssm_heads) == (36, 4, 64)
    assert full.layer_types.index("attention") == 5


def test_recipe_matches_the_program(small):
    cfg, s, _ = small
    weights.check_layout(s, M.abstract_params(cfg))


@pytest.mark.parametrize("path", ["mamba/in_proj", "attn/wq", "mlp/wo"])
def test_check_layout_names_a_stack_of_the_wrong_length(small, monkeypatch,
                                                        path):
    cfg, s, _ = small
    rec = H.recipe(s)
    leaf = rec[path]
    monkeypatch.setattr(H, "recipe", lambda _s: {
        **rec, path: leaf._replace(layers=leaf.layers + 1)})
    with pytest.raises(ValueError, match=path):
        weights.check_layout(s, M.abstract_params(cfg))


def test_layers_drawn_again_equal_the_served_weights(small):
    _, s, _ = small
    params = weights.make_params(s, SEED)
    again = weights.Redraw(s, SEED)
    for stack, layers in (("mamba", s.n_mamba), ("attn", s.n_attn),
                          ("mlp", s.n_layers)):
        for layer in range(layers):
            drawn = again.layer(layer, stack)
            served = jax.tree.map(lambda a: np.asarray(a[layer], np.float32),
                                  params[stack])
            jax.tree.map(np.testing.assert_array_equal, served, drawn)


def test_reference_equals_the_program_in_float32(small):
    cfg, s, prompts = small
    assert _gap_to_reference(cfg, s, prompts, _float32(s)) < TOL


def _no_residual_multiplier(cfg, s, params):
    return cfg.replace(residual_multiplier=1.0), params, None


def _no_d_skip(cfg, s, params):
    mamba = dict(params["mamba"], D=jnp.zeros_like(params["mamba"]["D"]))
    return cfg, dict(params, mamba=mamba), None


def _state_in_bf16(cfg, s, params):
    def decode_step(c, p, cache, tok):
        st = cache["ssm_state"].astype(jnp.bfloat16).astype(jnp.float32)
        return M.decode_step(c, p, dict(cache, ssm_state=st), tok)
    return cfg, params, decode_step


@pytest.mark.parametrize("fault", [_no_residual_multiplier, _no_d_skip,
                                   _state_in_bf16])
def test_planted_fault_fails_the_comparison(small, fault):
    cfg, s, prompts = small
    cfg, params, decode_step = fault(cfg, s, _float32(s))
    assert _gap_to_reference(cfg, s, prompts, params, decode_step) > 2 * TOL


def test_bf16_program_tokens_lie_within_rounding_of_the_best(small):
    cfg, s, prompts = small
    served, _ = _served(cfg, weights.make_params(s, SEED), prompts)
    gaps = reference.served_gaps(H.Reference(s, SEED), prompts, served)
    assert gaps.shape == (3, DECODE)
    assert gaps.min() == 0.0 and gaps.max() < 0.1


def test_quadratic_ssd_matches_the_recurrence():
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t, one step
    at a time, against the reference's masked quadratic form in blocks of
    query positions that do not divide the sequence."""
    b, t, nh, hd, g, n = 2, 13, 4, 3, 2, 5
    rng = np.random.default_rng(11)
    x = rng.standard_normal((b, t, nh, hd)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((b, t, nh)))).astype(np.float32)
    A = -np.exp(rng.standard_normal(nh)).astype(np.float32)
    B = rng.standard_normal((b, t, g, n)).astype(np.float32)
    C = rng.standard_normal((b, t, g, n)).astype(np.float32)
    rep = nh // g
    h = np.zeros((b, nh, hd, n), np.float64)
    want = np.zeros((b, t, nh, hd))
    for i in range(t):
        Bh = np.repeat(B[:, i], rep, axis=1)          # head -> its group
        Ch = np.repeat(C[:, i], rep, axis=1)
        h = np.exp(dt[:, i] * A)[..., None, None] * h \
            + dt[:, i][..., None, None] * x[:, i][..., None] \
            * Bh[:, :, None, :]
        want[:, i] = np.einsum("bhpn,bhn->bhp", h, Ch)
    got = H.ssd(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                jnp.asarray(B), jnp.asarray(C), block=4)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_chunk_counts_by_hand(small):
    """Prefill of 3 rows of 21 tokens (chunks of 8, 8, 5) and the decode
    call at position 23, at the small sizes: d 128, d_inner 256, 16 SSD
    heads of 16, d_state 16, one group, 6 Mamba-2 and 2 attention layers
    of 4 heads of 32 (2 kv), 8 MLPs of 128, vocab 256, bf16 weights."""
    _, s, _ = small
    rows, P = 3, 21
    in_proj = 128 * (2 * 256 + 2 * 16 + 16)
    mamba_mm = in_proj + 256 * 128
    attn_mm = 128 * 4 * 32 * 2 + 128 * 2 * 32 * 2
    mlp_mm = 3 * 128 * 128
    per_token = 6 * mamba_mm + 2 * attn_mm + 8 * mlp_mm
    pairs = 2 * (8 * 9 // 2) + 5 * 6 // 2
    ssd = 2 * pairs * (16 + 16 * 16) + 2 * 16 * 16 * 16 * (P - 8) \
        + 2 * 16 * 16 * 16 * P
    attn = 2 * 2 * 4 * 32 * (P * (P + 1) // 2)
    head = 2 * 128 * 256
    flops = 2 * per_token * rows * P + 6 * rows * ssd + 2 * rows * attn \
        + head * rows
    weights_b = (256 * 128 * 2 + 128 * 4                        # embed, norm
                 + 6 * (128 * 4 + in_proj * 2 + 4 * 288 * 2 + 288 * 2
                        + 3 * 16 * 4 + 256 * 4 + 256 * 128 * 2)
                 + 2 * (128 * 4 + attn_mm * 2)
                 + 8 * (128 * 4 + mlp_mm * 2))
    state = 6 * (16 * 16 * 16 * 4 + 3 * 288 * 2)
    kv_pos = 2 * 2 * 2 * 32 * 2
    pre_bytes = weights_b + rows * P * 128 * 2 + rows * state \
        + rows * P * kv_pos + rows * 256 * 2
    pre = H.prefill(s, rows, P)
    assert H.weight_bytes_per_call(s) == weights_b
    assert (pre.flops, pre.bytes) == (flops, pre_bytes)

    ctx = 24
    dec = H.decode(s, rows, 23)
    dec_flops = 2 * per_token * rows + 4 * 16 * 16 * 16 * 6 * rows \
        + 2 * 2 * 4 * 32 * ctx * rows * 2 + head * rows
    dec_bytes = weights_b + rows * 128 * 2 + 2 * rows * state \
        + rows * ctx * kv_pos + rows * kv_pos + rows * 256 * 2
    assert (dec.flops, dec.bytes) == (dec_flops, dec_bytes)
    p, d = H.chunk(s, rows, P, 3)
    assert p == pre and d == H.decode(s, rows, P) + H.decode(s, rows, P + 1)


def test_a_decode_call_at_the_cells_size_reads_7_6_gb():
    s = spec.config("granite-4.0-h-micro").sizes
    assert H.weight_bytes_per_call(s) == pytest.approx(6.383e9, rel=1e-3)
    dec = H.decode(s, 8, 2000)
    assert dec.bound(197e12, 819e9) == "bytes"
    assert 7.6e9 < dec.bytes < 7.8e9
    pre = H.prefill(s, 8, 1984)
    assert pre.bound(197e12, 819e9) == "flops"
    # weight products alone: 5.97 GFLOP per token
    assert pre.flops / (8 * 1984) == pytest.approx(5.97e9, rel=0.05)


def test_the_cell_rehearses_correct(monkeypatch):
    """The cell's whole run at the program's tiny sizes, with the traffic
    cut to a short prompt so that it runs in seconds here."""
    tr = spec.traffic("doc-chat-2k")
    short = dataclasses.replace(tr, prompt_len=19, decode_tokens=6,
                                check_sequences=4)
    monkeypatch.setattr(spec, "traffic", lambda _name: short)
    monkeypatch.setattr(spec, "check_limit", lambda _cell: 0.1)
    out = harness.run_cell("granite-4.0-h-micro.doc-chat-2k", 2**31 + 163,
                           0.3, False, 0.0, rehearse=True,
                           log=lambda _: None)
    assert out["correct"], out["limits"]
    assert out["attempted"] > 0 and out["failed"] == 0
