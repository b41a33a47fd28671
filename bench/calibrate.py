#!/usr/bin/env python3
"""Readings for a cell's correctness limits, in one process on the chip.

    python3 bench/calibrate.py <cell> --seconds S --seeds 11,12,... \
        [--control-seeds 21,22,23]

Runs the cell as `run_cell.run` does, window and check included, once per
seed of the configuration as stated and once per control seed of the
low-precision control (`run_cell.CONTROLS`: the program's own int4 prompt
path), and prints one JSON line per run with the numbers ``correct``
compares.  The lower reading of a limit is the largest that the sound runs
give, the upper the smallest that the control gives; the limit is set
between them by hand, in ``bench/limits/<cell>.json``.  The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    plan = [(int(s), None) for s in args.seeds.split(",") if s] + [
        (int(s), "int4_prefill") for s in args.control_seeds.split(",") if s]
    for seed, control in plan:
        t = time.perf_counter()
        r = run_cell.run(args.cell, seed, args.seconds, False, t_process=t,
                         control=control)
        print(json.dumps({"cell": args.cell, "seed": seed,
                          "control": control, "correct": r["correct"],
                          "check": r["check"], "readings": r["readings"],
                          "metrics": r["metrics"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "compiles_in_window": r["compiles_in_window"],
                          "memory_peak_bytes":
                              r["device"]["memory_peak_bytes"],
                          "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
