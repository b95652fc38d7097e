"""A configuration finds its family by file, and the common weight code
takes any recipe: leaves stacked over layers of different counts."""
import json

import numpy as np
import pytest

from chipbench import spec, weights
from chipbench.families import dense
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.models import model as M

SEED = 2**31 + 313


def _config_file(tmp_path, monkeypatch, **changes):
    """``configs/c.json``: stablelm-1.6b's file with ``changes``, in a data
    directory of its own (which has no families)."""
    d = json.loads((spec.HERE / "configs" / "stablelm-1.6b.json").read_text())
    d.update(changes)
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "c.json").write_text(json.dumps(d))
    monkeypatch.setattr(spec, "HERE", tmp_path)
    return d


def test_a_configuration_without_family_is_dense():
    conf = spec.config("stablelm-1.6b")
    assert type(conf.sizes) is dense.Sizes is spec.Sizes
    assert spec.family_of(conf.sizes) is dense is spec.family("dense")


def test_unknown_family_names_the_file_it_looked_for(tmp_path, monkeypatch):
    _config_file(tmp_path, monkeypatch, family="no-such")
    with pytest.raises(spec.SpecError, match="families/no-such.py"):
        spec.config("c")


def test_model_key_outside_the_family_sizes_is_an_error(tmp_path,
                                                         monkeypatch):
    d = json.loads((spec.HERE / "configs" / "stablelm-1.6b.json").read_text())
    model = dict(d["model"], n_experts=8)
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "dense.py").write_bytes(
        (spec.HERE / "families" / "dense.py").read_bytes())
    _config_file(tmp_path, monkeypatch, model=model)
    with pytest.raises(spec.SpecError, match="n_experts"):
        spec.config("c")


def test_reduced_key_outside_the_model_is_an_error(tmp_path, monkeypatch):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "dense.py").write_bytes(
        (spec.HERE / "families" / "dense.py").read_bytes())
    _config_file(tmp_path, monkeypatch, reduced=["num_hidden_layers"])
    with pytest.raises(spec.SpecError, match="num_hidden_layers"):
        spec.config("c")


@pytest.fixture
def small():
    cfg = reduced(get_config("stablelm-1.6b"))
    return cfg, dense.sizes_of(cfg)


def test_check_layout_names_a_leaf_stacked_over_other_layers(
        small, monkeypatch):
    cfg, s = small
    rec = dense.recipe(s)
    leaf = rec["blocks/mlp/wo"]
    monkeypatch.setattr(dense, "recipe", lambda _s: {
        **rec, "blocks/mlp/wo": leaf._replace(layers=leaf.layers + 1)})
    with pytest.raises(ValueError, match="blocks/mlp/wo") as err:
        weights.check_layout(s, M.abstract_params(cfg))
    assert "blocks/mlp/wi" not in str(err.value)


def test_two_stacks_of_different_lengths(small, monkeypatch):
    """A second stack of 3 layers beside the model's 2 (as a hybrid's
    attention layers beside its Mamba layers): served with its own
    leading axis, and drawn again layer by layer from its own prefix."""
    _, s = small
    rec = dense.recipe(s)
    extra = weights.Leaf((s.d_model, 4), 0.0, 1.0, True, 3)
    monkeypatch.setattr(dense, "recipe",
                        lambda _s: {**rec, "mixers/w": extra})
    params = weights.make_params(s, SEED)
    assert params["mixers"]["w"].shape == (3, s.d_model, 4)
    assert params["blocks"]["mlp"]["wo"].shape[0] == s.n_layers == 2
    again = weights.Redraw(s, SEED)
    for layer in range(3):
        np.testing.assert_array_equal(
            np.asarray(params["mixers"]["w"][layer], np.float32),
            again.layer(layer, "mixers")["w"])
    assert "mixers" not in again.layer(0) and "mixers" not in again.top()
    weights.check_layout(s, params)
