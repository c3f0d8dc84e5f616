"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name in the manifest, so a later PR
adds files and manifest entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

from perfbench import program_spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "perfbench")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Manifest:
    """The manifest at ``root`` (a checkout, or a temp tree in a test) with
    the benchmark's files under ``bench_dir``."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "perfbench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    # ------------------------------------------------------------ lookups
    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {[w['name'] for w in self.doc['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise SystemExit(f"perfbench: no config {name!r} in BENCHMARK.json")

    def traffic_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "traffic", name + ".json")

    def traffic(self, name: str) -> dict:
        with open(self.traffic_path(name)) as f:
            return json.load(f)

    def metrics_for(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader_path(self, metric: str) -> str:
        return os.path.join(self.bench_dir, "layer_metrics", metric + ".py")

    def reader(self, metric: str):
        """``read(ctx) -> float | None`` of one per-layer metric's file."""
        path = self.reader_path(metric)
        spec = importlib.util.spec_from_file_location(
            "perfbench_reader_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.bench_dir, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table or device_kind.startswith("_"):
            raise KeyError(f"no peaks known for device_kind {device_kind!r}; "
                           f"add it to perfbench/peaks.json with its source")
        return table[device_kind]


def resolve(dotted: str):
    """``"package.module:attr"`` -> the object."""
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


def layer_values(man: Manifest, cell: str, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader found something.
    A reader that finds nothing to read returns None and is left out. Where
    it asked ``program_spans`` / ``setup_spans`` for a span name that NO row
    of the run carries, ``ctx["program_lacks"]`` says so (metric -> those
    names): the program cannot write it, which is another thing than a
    stretch that held none (``every_listed_metric`` tells them apart)."""
    out = {}
    ctx["program_lacks"] = {}
    for m in man.metrics_for(cell, "per_layer"):
        value, in_vain = program_spans.asked_in_vain(
            ctx, man.reader(m["name"]))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        elif in_vain:
            ctx["program_lacks"][m["name"]] = in_vain
    return out
