"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric with NEW files and NEW manifest entries only: a throw-away set in a
temp directory, and the harness runs it at toy size on the CPU."""

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


def test_new_cell_config_mix_and_metric_need_only_new_files(tmp_path):
    root = str(tmp_path)
    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-7b-v0.3-serve1.json")) as f:
        config = json.load(f)
    config["name"] = "toy-wide"
    config["rehearsal"]["shape"]["num_hidden_layers"] = 1
    config["rehearsal"]["engine"] = {"max_slots": 2, "num_pages": 64,
                                     "max_len": 256}
    _write(os.path.join(root, "perfbench/configs/toy-wide.json"),
           json.dumps(config))
    _write(os.path.join(root, "perfbench/traffic/toy-mix.json"), json.dumps({
        "name": "toy-mix", "loop": "closed", "clients": 3, "cycle": 5,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.3,
                       "min": 8, "max": 20},
        "output_len": {"dist": "fixed", "value": 24}}))
    _write(os.path.join(root, "perfbench/layer_metrics/toy_tokens.py"), READER)
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps({
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
        "run_seconds": 2,
        "configs": [{"name": "toy-wide", "source": config["source"],
                     "file": "perfbench/configs/toy-wide.json",
                     "reduced": ["num_hidden_layers"], "why": "a test"}],
        "workloads": [{"name": "toy-cell", "config": "toy-wide",
                       "traffic": "toy-mix", "chips": 1, "why": "a test"}],
        "end_to_end": [
            {"name": "out_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "toy_tokens", "unit": "tokens", "better": "higher",
             "source": "program_counter", "layer": "engine host loop",
             "moves": "out_tokens_per_s"}]}))
    man = Manifest(root)
    assert "toy-cell" not in [w["name"] for w in Manifest(ROOT).doc["workloads"]]
    cell = man.cell("toy-cell")
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
