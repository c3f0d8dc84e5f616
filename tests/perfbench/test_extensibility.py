"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric with NEW files and NEW manifest entries only: a throw-away set in a
temp directory, and the harness runs it at toy size on the CPU. The second
set is of another family: its config class needs a STRING key of the
configuration file, and its reference check is its own file."""

import argparse
import json
import os
import time

from perfbench.manifest import BENCH_DIR, ROOT, Manifest
from perfbench.runners import serve as serve_runner

READER = '''"""Output tokens per completed request (a count)."""


def read(ctx):
    done = [s for s in ctx["run"]["sent"] if s.done]
    return sum(len(s.tokens) for s in done) / len(done) if done else None
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


E2E = [{"name": "out_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.05, "source": "host_clock"},
       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock"}]
TOY_TOKENS = {"name": "toy_tokens", "unit": "tokens", "better": "higher",
              "source": "program_counter", "layer": "engine host loop",
              "moves": "out_tokens_per_s"}
TOY_LOOP = {"name": "toy-loop", "loop": "closed", "clients": 2, "cycle": 4,
            "prompt_len": {"dist": "fixed", "value": 12},
            "output_len": {"dist": "fixed", "value": 16}}


def _base_config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-7b-v0.3-serve1.json")) as f:
        return json.load(f)


def _write_manifest(root, configs: dict, traffic: str, per_layer):
    """A manifest of one cell a configuration (``<name>-cell``), all under
    one mix; ``configs`` maps a name to its file's content."""
    for name, config in configs.items():
        _write(os.path.join(root, f"perfbench/configs/{name}.json"),
               json.dumps(config))
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps({
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
        "run_seconds": 2,
        "configs": [{"name": name, "source": config["source"],
                     "file": f"perfbench/configs/{name}.json",
                     "reduced": [], "why": "a test"}
                    for name, config in configs.items()],
        "workloads": [{"name": name + "-cell", "config": name,
                       "traffic": traffic, "chips": 1, "why": "a test"}
                      for name in configs],
        "end_to_end": E2E, "per_layer": per_layer}))
    return Manifest(root)


def test_new_cell_config_mix_and_metric_need_only_new_files(tmp_path):
    root = str(tmp_path)
    config = _base_config()
    config["name"] = "toy-wide"
    config["rehearsal"]["shape"]["num_hidden_layers"] = 1
    config["rehearsal"]["engine"] = {"max_slots": 2, "num_pages": 64,
                                     "max_len": 256}
    _write(os.path.join(root, "perfbench/traffic/toy-mix.json"), json.dumps({
        "name": "toy-mix", "loop": "closed", "clients": 3, "cycle": 5,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.3,
                       "min": 8, "max": 20},
        "output_len": {"dist": "fixed", "value": 24}}))
    _write(os.path.join(root, "perfbench/layer_metrics/toy_tokens.py"), READER)
    man = _write_manifest(root, {"toy-wide": config}, "toy-mix", [TOY_TOKENS])
    assert "toy-wide-cell" not in [w["name"]
                                   for w in Manifest(ROOT).doc["workloads"]]
    cell = man.cell("toy-wide-cell")
    args = argparse.Namespace(seed=3_000_000_007, seconds=2.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(man, cell, args, time.time())
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["metrics"]["toy_tokens"]["value"] == 24.0
    assert line["metrics"]["toy_tokens"]["unit"] == "tokens"
    assert "breakdown" not in line      # no device was traced

    args.trace = 0
    line = serve_runner.run(man, cell, args, time.time())
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}


FAMILY = '''"""A toy family of two layer kinds: the pattern string is the depth."""

import dataclasses

from ray_tpu.models.llama import LlamaConfig


@dataclasses.dataclass(frozen=True)
class PatternConfig(LlamaConfig):
    layer_pattern: str = ""

    def __post_init__(self):
        if not self.layer_pattern or set(self.layer_pattern) - set("AM"):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}")
        object.__setattr__(self, "n_layers", len(self.layer_pattern))


def check(engine, prompt, emitted, config, shape):
    """The family's own reference check: the sentinel reading says that
    THIS function ran, with the string key in ``shape`` and in the engine's
    config; the file's ``toy_check_passes`` decides ``ok``."""
    assert shape["hybrid_override_pattern"] == engine.cfg.layer_pattern
    assert engine.cfg.n_layers == len(shape["hybrid_override_pattern"])
    return {"ok": bool(shape["toy_check_passes"]), "finite": True,
            "readings": [{"name": "toy_sentinel_reading", "value": 27.0,
                          "limit": 27.5}],
            "notes": {"pattern": shape["hybrid_override_pattern"]}}
'''


def _pattern_config(config: dict, name: str, passes: bool) -> dict:
    config["name"] = name
    del config["num_hidden_layers"]
    config["hybrid_override_pattern"] = "MAMAMAMAMAMAMAMA"
    config["toy_check_passes"] = passes
    prog = config["program"]
    prog["config_class"] = "toy_pattern_family:PatternConfig"
    prog["reference_check"] = "toy_pattern_family:check"
    del prog["config_kwargs"]["n_layers"]
    prog["config_kwargs"]["layer_pattern"] = "hybrid_override_pattern"
    del config["rehearsal"]["shape"]["num_hidden_layers"]
    config["rehearsal"]["shape"]["hybrid_override_pattern"] = "MA"
    config["rehearsal"]["engine"] = {"max_slots": 2, "num_pages": 64,
                                     "max_len": 256}
    return config


def test_another_family_needs_only_new_files(tmp_path, monkeypatch, capfd):
    """A string shape key reaches the config class and the check, and the
    configuration's own check alone decides the reference part of
    ``correct``."""
    root = str(tmp_path)
    _write(os.path.join(root, "toy_pattern_family.py"), FAMILY)
    monkeypatch.syspath_prepend(root)     # the replica inherits sys.path
    _write(os.path.join(root, "perfbench/traffic/toy-loop.json"),
           json.dumps(TOY_LOOP))
    _write(os.path.join(root, "perfbench/layer_metrics/toy_tokens.py"), READER)
    man = _write_manifest(root, {
        name: _pattern_config(_base_config(), name, passes)
        for name, passes in (("toy-pattern", True),
                             ("toy-pattern-bad", False))},
        "toy-loop", [TOY_TOKENS])
    args = argparse.Namespace(seed=2_900_000_011, seconds=2.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(man, man.cell("toy-pattern-cell"), args,
                            time.time())
    out, err = capfd.readouterr()
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["toy_tokens"]["value"] == 16.0
    # the family's check ran, and no other: its reading is on both streams,
    # the dense check's are not
    assert "toy_sentinel_reading 27.00000 (limit 27.50000)" in out
    assert "compared: toy_sentinel_reading 27.00000 (limit 27.50000)" in err
    assert "pattern MA" in out and "prefill_max_abs_err" not in out

    args.trace = 0
    line = serve_runner.run(man, man.cell("toy-pattern-bad-cell"), args,
                            time.time())
    out, _ = capfd.readouterr()
    assert line["correct"] is False and line["failed"] == 0
    assert "toy_sentinel_reading" in out and "-> FAILED" in out


BROKEN = '''"""A config class whose module breaks the timed path underneath the
harness: every token the engine's step hands out is altered where it is
produced (the engine itself goes on decoding its own)."""

from ray_tpu.models import paged
from ray_tpu.models.llama import LlamaConfig

_step = paged.PagedEngine.step


def _altered_step(self):
    return [(rid, tok if tok is None else (tok + 1) % self.cfg.vocab_size)
            for rid, tok in _step(self)]


paged.PagedEngine.step = _altered_step
BrokenConfig = LlamaConfig
'''


def test_a_broken_timed_path_comes_out_not_correct(tmp_path, monkeypatch,
                                                   capfd):
    """The rest of a run as the driver starts it, but for the look for a
    chip: requests all finish, every token is a valid id, nothing compiles
    in the window, and the dense check alone sees that the served tokens
    are not the model's."""
    root = str(tmp_path)
    config = _base_config()
    config["name"] = "toy-broken"
    config["program"]["config_class"] = "toy_broken_family:BrokenConfig"
    config["rehearsal"]["engine"] = {"max_slots": 2, "num_pages": 64,
                                     "max_len": 256}
    _write(os.path.join(root, "toy_broken_family.py"), BROKEN)
    monkeypatch.syspath_prepend(root)
    _write(os.path.join(root, "perfbench/traffic/toy-loop.json"),
           json.dumps(TOY_LOOP))
    man = _write_manifest(root, {"toy-broken": config}, "toy-loop", [])
    args = argparse.Namespace(seed=2_900_000_033, seconds=2.0, trace=0,
                              rehearse=True)
    line = serve_runner.run(man, man.cell("toy-broken-cell"), args,
                            time.time())
    out, err = capfd.readouterr()
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["correct"] is False
    assert "compared: failed requests 0 (must be 0)" in err
    assert "compared: compilations inside the window 0 (must be 0)" in err
    assert "max_margin" in err and "-> FAILED" in out
