"""``program.shape_of``: which keys of a configuration file travel to the
config class, the reference and the readers."""

import json
import os

import pytest

from perfbench.manifest import BENCH_DIR
from perfbench.program import shape_of


def _file(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-serve1",
                                  "mistral-7b-v0.3-train4"])
@pytest.mark.parametrize("rehearse", [False, True])
def test_numeric_keys_travel_as_before(name, rehearse):
    """The two configurations the benchmark has keep their ``shape``: every
    top-level number, bool and null, and nothing else."""
    config = _file(name)
    want = {k: v for k, v in config.items()
            if isinstance(v, (int, float, bool)) or v is None}
    if rehearse:
        want.update(config["rehearsal"]["shape"])
    got = shape_of(config, rehearse)
    assert got == want
    assert all(isinstance(v, (int, float, bool)) or v is None
               for v in got.values())
    assert "hidden_act" not in got and "program" not in got


def test_a_named_key_of_any_json_type_travels():
    config = _file("mistral-7b-v0.3-serve1")
    config["hybrid_override_pattern"] = "MEMEM*EMEMEM*EME"
    config["layer_types"] = ["mamba", "moe", "attention"]
    config["unnamed_string"] = "stays behind"
    assert "hybrid_override_pattern" not in shape_of(config, False)
    kwargs = config["program"]["config_kwargs"]
    kwargs["layer_pattern"] = "hybrid_override_pattern"
    kwargs["kinds"] = "layer_types"
    shape = shape_of(config, False)
    assert shape["hybrid_override_pattern"] == "MEMEM*EMEMEM*EME"
    assert shape["layer_types"] == ["mamba", "moe", "attention"]
    assert "unnamed_string" not in shape and "hidden_act" not in shape
    assert shape["hidden_size"] == 4096


def test_rehearsal_overrides_a_named_string():
    config = _file("mistral-7b-v0.3-serve1")
    config["hybrid_override_pattern"] = "MEMEM*EMEMEM*EME"
    config["program"]["config_kwargs"]["layer_pattern"] = \
        "hybrid_override_pattern"
    config["rehearsal"]["shape"]["hybrid_override_pattern"] = "ME*"
    assert shape_of(config, True)["hybrid_override_pattern"] == "ME*"
    assert shape_of(config, False)["hybrid_override_pattern"] \
        == "MEMEM*EMEMEM*EME"
