"""The dense decoder's reference check at a toy width on the CPU: a sound
engine passes it, the control (the program's own int8 weights in the
engine's place) fails it, and so does one altered token."""

import math

import pytest

from perfbench.manifest import ROOT, Manifest
from perfbench.reference import dense_check, dense_control

SEEDS = [31, 32, 33]


@pytest.fixture(scope="module")
def config():
    config = Manifest(ROOT).config("mistral-7b-v0.3-serve1")
    # the cell's depth: the roundings of sixteen layers reach the logits
    config["rehearsal"]["shape"]["num_hidden_layers"] = 16
    return config


@pytest.fixture(scope="module")
def by_seed(config):
    return {seed: dense_control.one_seed(config, seed, rehearse=True)
            for seed in SEEDS}


def _values(result):
    return {r["name"]: r["value"] / result["notes"]["ref_logit_std"]
            for r in result["readings"]}


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_engine_passes(by_seed, seed):
    r = by_seed[seed]["sound"]
    assert r["ok"] is True and r["finite"] is True
    assert [x["name"] for x in r["readings"]] == [
        "prefill_max_abs_err", "max_margin", "prefill_rms_err"]
    assert all(math.isfinite(x["value"]) and 0 <= x["value"] <= x["limit"]
               for x in r["readings"])
    assert r["notes"]["tokens"] == dense_control.REF_NEW


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_weights_in_the_engine_fail(by_seed, seed):
    """Weights through the program's own int8 quantiser and back: the
    reference still sees the seed's weights, and one reading is over."""
    sound, control = by_seed[seed]["sound"], by_seed[seed]["w8"]
    assert control["ok"] is False and control["finite"] is True
    over = [x["name"] for x in control["readings"] if x["value"] > x["limit"]]
    assert "prefill_rms_err" in over
    assert _values(control)["prefill_rms_err"] \
        > 1.5 * _values(sound)["prefill_rms_err"]


def test_one_altered_token_fails(config):
    """A token changed where it is produced: its reference logit lies far
    under the reference's best at that position."""
    from ray_tpu.models.paged import PagedEngine

    from perfbench import program, traffic as tg

    shape = program.shape_of(config, True)
    cfg = program.model_config(config, shape)
    engine = PagedEngine(program.init_weights(config, cfg, 41), cfg,
                         max_slots=2, num_pages=64, page_size=16, max_len=256)
    prompt = tg.prompt_tokens(41, 5, 200, shape["vocab_size"])
    engine.submit("r", prompt, max_new_tokens=24)
    emitted = engine.run_to_completion()["r"]
    assert dense_check.check(engine, prompt, emitted, config, shape)["ok"]
    emitted[7] = (emitted[7] + 1) % shape["vocab_size"]
    r = dense_check.check(engine, prompt, emitted, config, shape)
    margin = next(x for x in r["readings"] if x["name"] == "max_margin")
    assert r["ok"] is False and margin["value"] > 3 * margin["limit"]
