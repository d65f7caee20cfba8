#!/usr/bin/env python3
"""Readings that set a fit cell's correctness limits, taken on the chip.

    python bench/control.py --workload pol.fit_tol --seconds 20 \\
        --seeds 1-12 --control-seeds 101-103 [--faults unchanged,altered \\
        --fault-seeds 201-203]

For every seed the cell's traffic runs at the cell's own size for
``--seconds`` (the run's length, so that as many fits are compared as a run
compares) and the check's numbers of every fit are printed, one JSON line
per seed with the worst of each. Set-up is repeated per seed; after the
first seed of a mode its programs come from memory. Sound seeds run the
program as it is; the control seeds run it under
``bench.faults.control_bf16``; fault seeds under each named fault. The last line holds, per number, the lower reading (the
largest over sound seeds) and the upper (the smallest over control seeds).
All modes run in one process, so the programs compile once per mode.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def seeds(text: str) -> list[int]:
    """'1-12' or '1,5,9' (or a mix) to a list of whole numbers."""
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def readings(cell, seed_list, seconds: float, mode: str) -> list[dict]:
    from bench import check
    from bench.generators import closed_loop_fits as gen

    rows = []
    for seed in seed_list:
        prep = gen.setup(cell, seed)
        win = gen.measure(prep, seconds)
        per_fit = [check.fit_numbers(prep.x, prep.y, cell.config, out)
                   for out in win.outputs]
        worst = {name: max(f[name] for f in per_fit) for name in cell.limits}
        row = {"mode": mode, "seed": seed, "fits": win.attempted,
               "failed": win.failed, "worst": worst, "per_fit": per_fit}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control-max-epochs", type=float, default=None,
                    help="cap the control's solver epochs per step, so that "
                    "a control that cannot reach the tolerance still ends")
    args = ap.parse_args(argv)

    from bench import run
    from bench.faults import FAULTS
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    run.enable_cache()
    run.device_info(cell.chips)
    sound = readings(cell, seeds(args.seeds), args.seconds, "sound")
    if args.control_max_epochs is not None:
        cell.traffic = dict(cell.traffic,
                            max_epochs=args.control_max_epochs)
    with FAULTS["control_bf16"]():
        control = readings(cell, seeds(args.control_seeds), args.seconds,
                           "control_bf16")
    for name in filter(None, args.faults.split(",")):
        with FAULTS[name]():
            readings(cell, seeds(args.fault_seeds), args.seconds, name)
    summary = {name: {"lower": max((r["worst"][name] for r in sound),
                                   default=None),
                      "upper": min((r["worst"][name] for r in control),
                                   default=None)}
               for name in cell.limits}
    print(json.dumps({"workload": args.workload, "readings": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
