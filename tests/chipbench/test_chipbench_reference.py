"""The plain float32 reference against the program's prefill and cached
decode, at the program's tiny same-family sizes, for both norm / RoPE /
GQA variants of the benchmark's configurations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights
from chipbench.harness import sizes_of
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.models import model as M

PROMPT, DECODE, SEED = 12, 6, 2**31 + 77


def _program_decode(cfg, params, prompts):
    """Greedy prefill + cached decode: served tokens and the logits each
    was chosen from."""
    logits, cache = M.prefill(cfg, params, jnp.asarray(prompts), None,
                              max_len=32)
    seen = [np.asarray(logits[:, -1], np.float32)]
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    toks = [tok]
    for _ in range(DECODE - 1):
        logits, cache = M.decode_step(cfg, params, cache, tok)
        seen.append(np.asarray(logits[:, -1], np.float32))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        toks.append(tok)
    return np.asarray(jnp.concatenate(toks, 1)), np.stack(seen, 1)


@pytest.fixture(params=["stablelm-1.6b", "yi-6b"])
def model(request):
    cfg = reduced(get_config(request.param))
    s = sizes_of(cfg)
    weights.check_layout(s, M.abstract_params(cfg))
    prompts = np.random.default_rng(0).integers(
        0, s.vocab, (3, PROMPT)).astype(np.int32)
    return cfg, s, prompts


def test_configs_differ_where_the_reference_branches(model):
    cfg, s, _ = model
    if cfg.arch_id == "yi-6b":
        assert (s.norm_type, s.rope_fraction) == ("rmsnorm", 1.0)
    else:
        assert (s.norm_type, s.rope_fraction) == ("layernorm", 0.25)
    assert s.n_heads > s.n_kv_heads            # grouped-query attention


def test_layers_drawn_again_equal_the_served_weights(model):
    _, s, _ = model
    params = weights.make_params(s, SEED)
    again = weights.Redraw(s, SEED)
    for layer in range(s.n_layers):
        lw = again.layer(layer)
        for group, names in (("attn", ("wq", "wk", "wv", "wo")),
                             ("mlp", ("wi", "wg", "wo"))):
            for n in names:
                served = np.asarray(params["blocks"][group][n][layer],
                                    np.float32)
                np.testing.assert_array_equal(served, lw[group][n])
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32), again.top()["embed"])


def test_reference_equals_the_program_in_float32(model):
    cfg, s, prompts = model
    cfg32 = cfg.replace(dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_params(s, SEED))
    served, seen = _program_decode(cfg32, params, prompts)
    ref = reference.Reference(s, SEED)
    want = ref.logits(np.concatenate([prompts, served[:, :-1]], 1),
                      PROMPT - 1)
    # the program's float32 path vs the reference: both exact float32
    # arithmetic on the same values, apart from summation order
    np.testing.assert_allclose(seen, want, atol=2e-4, rtol=0)


def test_bf16_program_tokens_lie_within_rounding_of_the_best(model):
    cfg, s, prompts = model
    served, _ = _program_decode(cfg, weights.make_params(s, SEED), prompts)
    gaps = reference.served_gaps(reference.Reference(s, SEED), prompts,
                                 served)
    assert gaps.shape == (3, DECODE)
    assert gaps.min() == 0.0 and gaps.max() < 0.1


def test_control_and_altered_tokens_read_far_wider():
    cfg = reduced(get_config("yi-6b"))
    s = sizes_of(cfg)
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, s.vocab, (8, PROMPT)).astype(np.int32)
    served, _ = _program_decode(cfg, weights.make_params(s, SEED), prompts)
    ref = reference.Reference(s, SEED)
    program = reference.served_gaps(ref, prompts, served).max()
    control = reference.control_gaps(ref, prompts, served).max()
    altered = served.copy()
    altered[:, DECODE // 2] = (altered[:, DECODE // 2] + 1) % s.vocab
    wrong = reference.served_gaps(ref, prompts, altered).max()
    assert control > 3 * program
    assert wrong > 3 * program
