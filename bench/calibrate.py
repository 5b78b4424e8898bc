"""Readings that the correctness limits are set from, at a cell's own size,
in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

runs the program on ``--seeds`` seeds (the lower readings), the control
(the reference in bfloat16 in the program's place) and every planted
fault of ``faults.py`` on ``--control-seeds`` seeds each (the upper
readings); the policy faults only where the cell's configuration declares
``policies``. Each run is a benchmark run whose window is the first replay
alone. One JSON line per run: what ran, on which seed, and every number
the check compares. Not part of a benchmark run; run it where the cell
runs, on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--only", default="",
                    help="comma-separated subset of program,control and "
                         "the fault names")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_cache_dir()
    sys.path.insert(0, str(run.ROOT / "src"))
    import faults
    plans = [("program", None, args.seeds),
             ("control", lambda: faults.control(cell.config),
              args.control_seeds)]
    named = dict(faults.FAULTS)
    if cell.config.get("policies"):
        named.update(faults.POLICY_FAULTS)
    plans += [(name, make, args.control_seeds)
              for name, make in named.items()]
    only = set(filter(None, args.only.split(",")))
    for name, make, n in plans:
        if only and name not in only:
            continue
        for i in range(n):
            seed = args.first_seed + 1000 * i + 17
            t0 = time.perf_counter()
            try:
                out = run.run_cell(cell, seed, 0.0, False, t0,
                                   patches=[make()] if make else [])
                line = {"run": name, "seed": seed, "correct": out["correct"],
                        "numbers": {k: v["value"]
                                    for k, v in out["checks"].items()},
                        "metrics": {k: v["value"]
                                    for k, v in out["metrics"].items()}}
            except Exception as e:               # a crash counts as failed
                line = {"run": name, "seed": seed, "correct": False,
                        "error": f"{type(e).__name__}: {e}"}
            line["seconds"] = time.perf_counter() - t0
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
