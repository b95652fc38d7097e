"""The dense family reads what it read before it became a file of its own.

``data/golden_dense.json`` and ``data/golden_dense_logits.npz`` were
recorded while the dense code still lived in ``weights.py``,
``reference.py`` and ``work.py``, by running this file as a program:

    JAX_PLATFORMS=cpu PYTHONPATH=src:. \\
        python3 tests/chipbench/test_chipbench_golden.py

They hold, at the program's tiny same-family sizes of both norm types, a
digest of every weight leaf and the reference's float32 and fp8 logits on
a fixed batch; and, at the published sizes, the work of one chunk at every
row count the four cells' traffic can put in a chunk. Weights and logits
must match bit for bit on the CPU, the work counts exactly: a family
file that moved anything the benchmark reads fails here. Recording them
again from the moved code would prove nothing.
"""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, reference, spec, weights, work

DATA = Path(__file__).resolve().parent / "data"
WEIGHTS_AND_WORK = DATA / "golden_dense.json"
LOGITS = DATA / "golden_dense_logits.npz"
ARCHS = ["stablelm-1.6b", "yi-6b"]          # layernorm, rmsnorm
SEED = 2**31 + 4093
FIRST = 4


def _small(arch):
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    return harness.sizes_of(reduced(get_config(arch)))


def _tokens(s):
    return np.random.default_rng(11).integers(
        0, s.vocab, (2, 10)).astype(np.int32)


def weight_digests(arch):
    params = weights._flatten(weights.make_params(_small(arch), SEED))
    return {path: [str(leaf.dtype), list(leaf.shape),
                   hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()]
            for path, leaf in sorted(params.items())}


def logits(arch, fp8):
    s = _small(arch)
    return reference.Reference(s, SEED).logits(_tokens(s), FIRST, fp8=fp8)


def chunk_work():
    """Per cell, per row count: prefill FLOPs and bytes, decode FLOPs and
    bytes of one chunk at the cell's prompt length and tokens out."""
    bench = spec.load_benchmark()
    out = {}
    for w in bench["workloads"]:
        s = spec.config(w["config"]).sizes
        tr = spec.traffic(w["traffic"])
        rows = {}
        for n in range(1, tr.batch_jobs * tr.items_per_job + 1):
            pre, dec = work.chunk(s, n, tr.prompt_len, tr.decode_tokens)
            rows[str(n)] = [pre.flops, pre.bytes, dec.flops, dec.bytes]
        out[w["name"]] = rows
    return out


@pytest.fixture(scope="module")
def golden():
    return json.loads(WEIGHTS_AND_WORK.read_text())


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_are_bit_identical(golden, arch):
    assert weight_digests(arch) == golden["weights"][arch]


@pytest.mark.parametrize("fp8", [False, True], ids=["float32", "fp8"])
@pytest.mark.parametrize("arch", ARCHS)
def test_reference_logits_are_bit_identical(arch, fp8):
    want = np.load(LOGITS)[f"{arch}.{'fp8' if fp8 else 'float32'}"]
    have = logits(arch, fp8)
    assert have.dtype == want.dtype and have.shape == want.shape
    assert have.tobytes() == want.tobytes(), \
        f"largest difference {np.abs(have - want).max()}"


def test_chunk_work_is_exactly_equal(golden):
    assert chunk_work() == golden["work"]


def _record():
    DATA.mkdir(exist_ok=True)
    WEIGHTS_AND_WORK.write_text(json.dumps(
        {"seed": SEED, "weights": {a: weight_digests(a) for a in ARCHS},
         "work": chunk_work()}, indent=1, sort_keys=True) + "\n")
    np.savez(LOGITS, **{f"{a}.{'fp8' if f else 'float32'}": logits(a, f)
                        for a in ARCHS for f in (False, True)})


if __name__ == "__main__":
    _record()
