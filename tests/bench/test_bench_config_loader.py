"""How a benchmark config file becomes the program's ModelConfig
(`bench/lib/weights.py` `model_config`): every field the file states is
read as stated, a field it leaves out takes the dataclass's default, a
required field cannot be left out, and a left-out key that changes the
registered model must be listed in `reduced` like any other cut.  And
the reference controls that `bench/calibrate.py` finds in each family's
module."""
import copy
import dataclasses
import inspect
import json
import os

import pytest

from bench.lib import weights
from bench.lib.spec import Bench

from conftest import REPO

ACCEPTED = ["mamba2-780m", "mistral-nemo-12b-pp4",
            "granite-4.0-h-small-ep8-pp4"]
REQUIRED = ["family", "n_layers", "d_model", "n_heads", "n_kv_heads",
            "d_ff", "vocab"]


def load(name: str) -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _fields():
    from repro.configs.base import ModelConfig
    return [f for f in dataclasses.fields(ModelConfig) if f.name != "name"]


@pytest.mark.parametrize("name", ACCEPTED)
def test_an_accepted_file_loads_every_field_as_it_states_it(name):
    """Each field is the file's value; a sub-config group's stated keys
    are its values, and one the file leaves out is its default."""
    conf = load(name)
    cfg = weights.model_config(conf)
    assert cfg.name == conf["arch"]
    for f in _fields():
        value, stated = getattr(cfg, f.name), conf.get(f.name, f.default)
        if isinstance(stated, dict):
            assert dataclasses.is_dataclass(value), f.name
            kind = type(value)
            assert value == kind(**stated), f.name
            for key, v in stated.items():
                assert getattr(value, key) == v, (f.name, key)
        else:
            assert value == stated, f.name


def test_a_file_without_fields_at_their_default_loads_as_the_whole():
    conf = load("mamba2-780m")
    cut = copy.deepcopy(conf)
    del cut["audio"], cut["sliding_window"]
    assert weights.model_config(cut) == weights.model_config(conf)


@pytest.mark.parametrize("drop, named", [
    (("tie_embeddings",), "tie_embeddings"),   # default False, registry True
    (("attn_every",), "attn_every"),           # default 1, registry 10
    (("ssm", "conv_bias"), "ssm"),             # the sub-config rule
    (("ssm", "norm_before_gate"), "ssm"),
])
def test_a_left_out_key_that_changes_the_registered_model_raises(drop,
                                                                 named):
    """The default, not the registry's value, stands in for a left-out
    key, so leaving out a key whose registered value differs is a cut
    like any other: unlisted in `reduced`, it raises and names the key."""
    conf = load("granite-4.0-h-small-ep8-pp4")
    group = conf
    for key in drop[:-1]:
        group = group[key]
    del group[drop[-1]]
    with pytest.raises(ValueError, match=named):
        weights.model_config(conf)


@pytest.mark.parametrize("key", REQUIRED)
def test_a_file_without_a_required_field_raises_naming_it(key):
    conf = load("mistral-nemo-12b-pp4")
    del conf[key]
    with pytest.raises(ValueError) as err:
        weights.model_config(conf)
    assert repr(key) in str(err.value)
    assert "mistral-nemo-12b-pp4.json" in str(err.value)


def test_the_required_fields_are_those_without_a_default():
    assert [f.name for f in _fields()
            if f.default is dataclasses.MISSING] == REQUIRED


@pytest.mark.parametrize("workload, controls", [
    ("mamba2_offline", {"ssm_state_bf16": {"state_dtype": "bfloat16"}}),
    ("granite_h_offline", {"ssm_state_bf16": {"state_dtype": "bfloat16"}}),
    ("nemo_offline", {}),
])
def test_calibrate_finds_each_family_s_reference_controls(workload,
                                                          controls):
    """calibrate reads a family's controls from families/<family>.py,
    and the family's reference `logits` takes each control's options."""
    from bench.calibrate import state_controls
    bench = Bench()
    fam = bench.config(bench.workload(workload)["config"])["family"]
    assert state_controls(fam) == controls
    logits = bench.reference(fam).logits
    for opts in controls.values():
        inspect.signature(logits).bind(None, None, None, **opts)
