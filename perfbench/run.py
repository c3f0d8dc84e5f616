"""One run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds`` and prints, as the last line of
standard output, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``. It needs the
cell's chips: without them it exits non-zero and prints no result.
``--rehearse`` walks the same paths at toy widths on the CPU (four virtual
devices for a four-chip cell); its line says ``"platform": "cpu"`` and holds
counts only, never a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from perfbench.manifest import Manifest, resolve

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    man = Manifest(ROOT)
    if args.seconds is None:
        args.seconds = float(man.doc["run_seconds"])
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    runner = resolve(config["runner"])
    line = runner(man, cell, args, T_START)
    if args.rehearse:
        # a CPU run yields counts, never a time, rate or share of the device
        counts = {m["name"] for m in man.doc["per_layer"]
                  if m["source"] == "program_counter"}
        print("[perfbench] rehearsal values, not measurements: "
              + json.dumps(line["metrics"]), flush=True)
        line["metrics"] = {k: v for k, v in line["metrics"].items()
                           if k in counts}
        line["rehearsal"] = True
        line.pop("breakdown", None)
        for key in ("busy_s", "window_s"):
            line["device"].pop(key, None)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
