"""BENCHMARK.json against the files it names and the contract's limits."""

import json
import os

import pytest

from perfbench.manifest import NAME_RE, ROOT, UNIT_RE, Manifest

MAN = Manifest(ROOT)
DOC = MAN.doc
E2E = {m["name"]: m for m in DOC["end_to_end"]}
CELLS = [w["name"] for w in DOC["workloads"]]


def _cells_of(metric):
    return set(metric.get("workloads", CELLS))


def test_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51
    runs = 2 + 14 * 24      # the limit is what fits with the full 24 cells
    assert runs * (DOC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(DOC)) < 64 * 1024
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_text(kind):
    names = [e["name"] for e in DOC[kind]]
    assert len(names) == len(set(names))
    for e in DOC[kind]:
        assert NAME_RE.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT_RE.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key]


@pytest.mark.parametrize("cell", DOC["workloads"], ids=CELLS)
def test_cell_has_its_files_and_metrics(cell):
    assert cell["chips"] in (1, 4)
    assert NAME_RE.match(cell["config"]) and NAME_RE.match(cell["traffic"])
    config = MAN.config(cell["config"])
    assert config["chips"] == cell["chips"]
    assert os.path.isfile(MAN.traffic_path(cell["traffic"]))
    assert MAN.traffic(cell["traffic"])["name"] == cell["traffic"]
    e2e = [m["name"] for m in MAN.metrics_for(cell["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert MAN.metrics_for(cell["name"], "per_layer")


@pytest.mark.parametrize("entry", DOC["configs"],
                         ids=[c["name"] for c in DOC["configs"]])
def test_config_file(entry):
    assert entry["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert any(w["config"] == entry["name"] for w in DOC["workloads"])
    widths = ("hidden_size", "intermediate_size", "head_dim")
    for key in entry["reduced"]:
        assert key in config and key not in widths
        assert not key.endswith(("_dim", "_rank"))
    # what is not listed as reduced is as published
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_hidden_layers": 32, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "head_dim": 128,
                 "vocab_size": 32768, "rope_theta": 1000000.0,
                 "rms_norm_eps": 1e-05, "tie_word_embeddings": False}
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert config[key] == value, key


@pytest.mark.parametrize("metric", DOC["per_layer"],
                         ids=[m["name"] for m in DOC["per_layer"]])
def test_layer_metric_has_a_reader_and_moves_something_reported(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert callable(MAN.reader(metric["name"]))
    assert _cells_of(metric) <= set(CELLS)
    assert _cells_of(metric) <= _cells_of(E2E[metric["moves"]])
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_every_file_under_layer_metrics_and_traffic_is_named():
    readers = {f[:-3] for f in os.listdir(
        os.path.join(MAN.bench_dir, "layer_metrics")) if f.endswith(".py")}
    assert readers == {m["name"] for m in DOC["per_layer"]}
    mixes = {f[:-5] for f in os.listdir(
        os.path.join(MAN.bench_dir, "traffic")) if f.endswith(".json")}
    assert mixes == {w["traffic"] for w in DOC["workloads"]}
    for path in DOC["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert MAN.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        MAN.peaks("TPU v9")
