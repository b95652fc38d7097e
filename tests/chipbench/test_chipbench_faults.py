"""A whole run with the timed path broken underneath reads not correct.

Skips only the look for a chip (``rehearse``): the program's tiny
same-family model serves the cell's traffic through the real queue,
scheduler and executor, and the check compares what it served with the
plain reference. Each fault a served cell can have is planted in the
program's decode step, where tokens and state are produced. (A replica
exchange does not exist here: federated runtimes share nothing.)
"""
import pytest

from chipbench import harness, spec
from repro.models import model as M
from repro.serve.engine import HeteroServeEngine

CELL = "yi-6b.chat-decode"
#: the widest gap the tiny model's sound runs read is ~0.03 (the reduced
#: sizes round far less than the published widths); 0.1 sits above it
SMALL_LIMIT = 0.1


def _run(monkeypatch, decode_step, log=lambda _: None):
    monkeypatch.setattr(spec, "check_limit", lambda _cell: SMALL_LIMIT)
    monkeypatch.setattr(M, "decode_step", decode_step)
    return harness.run_cell(CELL, 2**31 + 21, 0.3, False, 0.0,
                            rehearse=True, log=log)


def _altered_token(cfg, params, cache, tokens):
    logits, cache = _sound(cfg, params, cache, tokens)
    return logits.at[..., 7].add(1e4), cache


def _state_unchanged(cfg, params, cache, tokens):
    logits, _ = _sound(cfg, params, cache, tokens)
    return logits, cache


_sound = M.decode_step


def test_sound_run_is_correct(monkeypatch):
    out = _run(monkeypatch, _sound)
    assert out["correct"], out["limits"]
    assert out["limits"]["sequences_of_wrong_length"] == [0, 0]


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_fault_reads_not_correct(monkeypatch, fault):
    out = _run(monkeypatch, fault)
    gap, limit = out["limits"]["widest_logit_gap"]
    assert not out["correct"] and gap > limit


def test_last_token_dropped_reads_not_correct(monkeypatch):
    """A decode loop one call short returns 63 of 64 tokens: the check
    counts the short sequences and the token rate counts what came back."""
    init = HeteroServeEngine.__init__

    def one_short(self, *a, **k):
        init(self, *a, **k)
        self.decode_tokens -= 1

    monkeypatch.setattr(HeteroServeEngine, "__init__", one_short)
    lines = []
    out = _run(monkeypatch, _sound, lines.append)
    wrong, limit = out["limits"]["sequences_of_wrong_length"]
    assert not out["correct"] and wrong > limit
    window = next(x for x in lines if x.startswith("window "))
    short = spec.traffic("chat-decode").decode_tokens - 1
    assert f" jobs, {out['attempted'] * short} tokens;" in window


def test_control_in_the_programs_place_reads_not_correct(monkeypatch):
    """The fp8 control, read on the same served sequences, fails the
    limit that the sound program meets."""
    monkeypatch.setattr(spec, "check_limit", lambda _cell: SMALL_LIMIT)
    out = harness.run_cell(CELL, 2**31 + 22, 0.3, False, 0.0,
                           rehearse=True, control=True, log=lambda _: None)
    assert out["correct"] and not out["control"]["control_correct"]
    assert out["control"]["program_gap"] <= SMALL_LIMIT \
        < out["control"]["control_gap"]


def test_check_draws_from_distinct_sequences():
    """Waves repeat request rows; the check's pool holds each row and its
    served tokens once, so every pick is a different sequence."""
    import numpy as np
    from repro.configs.base import reduced
    from repro.configs.registry import get_config

    sizes = harness.sizes_of(reduced(get_config(spec.config("yi-6b").arch)))
    tr = spec.traffic("chat-decode")
    rng = np.random.default_rng(3)
    toks = rng.integers(0, sizes.vocab, (4, tr.decode_tokens))
    samples = [([0, 1, 2, 3], toks)] * 5 + [([0, 1], toks[:2])]
    *_, distinct = harness._check(samples, 2**31 + 23, sizes, tr, False)
    assert distinct == 4
