"""The rest of a training run as the driver starts it, but for the look for
a chip (toy widths, four virtual CPU devices): sound, `correct` is true;
with the timed step broken underneath, so that its update does nothing
useful, the window's own losses make it false."""

import argparse
import json
import os
import time

import pytest

from perfbench.manifest import BENCH_DIR, Manifest
from perfbench.runners import train as train_runner

BROKEN = '''"""The program's loss with its gradient cut: the sharded step runs, the
optimizer is handed zeros, and the state goes nowhere the loss can see."""

import jax

from ray_tpu.models.llama import loss_fn as _loss_fn


def loss_fn(params, batch, cfg, **kw):
    return _loss_fn(jax.lax.stop_gradient(params), batch, cfg, **kw)
'''


def _tree(root, loss=None):
    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-7b-v0.3-train4.json")) as f:
        config = json.load(f)
    if loss:
        config["program"]["loss"] = loss
    for rel, text in (
            ("perfbench/configs/toy-train.json", json.dumps(config)),
            ("perfbench/traffic/fixed-2x4096.json", open(os.path.join(
                BENCH_DIR, "traffic", "fixed-2x4096.json")).read()),
            ("toy_cut_gradient.py", BROKEN),
            ("BENCHMARK.json", json.dumps({
                "command": ["python3", "perfbench/run.py"],
                "paths": ["perfbench"], "run_seconds": 2,
                "configs": [{"name": "toy-train", "source": config["source"],
                             "file": "perfbench/configs/toy-train.json",
                             "reduced": [], "why": "a test"}],
                "workloads": [{"name": "toy-train-cell", "config": "toy-train",
                               "traffic": "fixed-2x4096", "chips": 4,
                               "why": "a test"}],
                "end_to_end": [
                    {"name": "train_tokens_per_s", "unit": "tokens/s",
                     "better": "higher", "bound": 0.01,
                     "source": "host_clock"},
                    {"name": "setup_s", "unit": "s", "better": "lower",
                     "bound": 0.1, "source": "host_clock"}],
                "per_layer": []}))):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    return Manifest(root)


@pytest.mark.parametrize("loss,want", [
    (None, True), ("toy_cut_gradient:loss_fn", False)])
def test_the_window_decides(tmp_path, monkeypatch, capfd, loss, want):
    root = str(tmp_path)
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.syspath_prepend(root)
    man = _tree(root, loss)
    args = argparse.Namespace(seed=3_300_000_017, seconds=2.0, trace=0,
                              rehearse=True)
    line = train_runner.run(man, man.cell("toy-train-cell"), args,
                            time.time())
    out, err = capfd.readouterr()
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert line["correct"] is want
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # every number that decided it is on standard error beside its limit
    assert "reference check at depth 2" in out and "-> ok" in out
    assert "compared: window's lowest loss" in err
    assert "compared: window steps that repeat the loss before them" in err
    assert "compared: compilations inside the window 0 (must be 0)" in err


def test_the_readings_script_reads_the_cells_own_step():
    """``perfbench/train_readings.py`` at toy widths: one JSON line a seed,
    every loss of the cell's compiled step and what the runner compares."""
    import subprocess
    import sys

    from perfbench.manifest import ROOT

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "train_readings.py"),
         "--cell", "train-fsdp2-tp2", "--seeds", "3300000019:3,3300000023",
         "--steps", "6", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    assert [r["steps"] for r in rows] == [3, 6]
    assert all(len(r["losses"]) == r["steps"] for r in rows)
    assert all(ok for _, ok in rows[1]["compared"][:2])
    assert rows[1]["lowest_over_seeded"] < 1.0
