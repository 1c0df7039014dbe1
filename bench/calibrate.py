"""Readings that the correctness limit of a cell is set from.

    python bench/calibrate.py --workload qwen32b-chat --seconds 30 \
        --seeds 1,2,3 --control 1,2,3

Runs the cell once per seed in one process, as ``run.py`` does, and prints
one JSON line per seed: the program's widest logit gap and, for the seeds
in ``--control``, the int4 control's widest gap on the same positions.
The limit lies above the largest program reading and below the smallest
control reading.  ``--layers N`` cuts the configuration to its first N
layers, to see how the gap grows with depth.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402
import spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--layers", type=int, default=0)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    if a.layers:
        cell["config"] = dict(cell["config"], num_hidden_layers=a.layers)
    ctl = {int(s) for s in a.control.split(",") if s}
    for seed in (int(s) for s in a.seeds.split(",")):
        res = R.run(cell, seed, a.seconds, False, control=seed in ctl)
        print(json.dumps({
            "seed": seed,
            "max_logit_gap": res["checks"]["max_logit_gap"]["value"],
            "served_tokens_compared":
                res["checks"]["served_tokens_compared"]["value"],
            "control": res.get("control"),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)


if __name__ == "__main__":
    main()
