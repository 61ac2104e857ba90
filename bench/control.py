#!/usr/bin/env python3
"""Readings that set the check's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds a b c

For each seed, in one process on the chip: a whole run of the cell (set-up,
a window at the cell's own load, the reference check), then the control
in the program's place, checked by the same comparison (the kind's
``control``; for read mapping, the reference's own answers for the same
sampled requests, computed with scores rounded through float8, e4m3).
One JSON line per seed, then the summary: each number's lower reading
(the largest the program gave) and upper reading (the smallest the
control gave).  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import run, spec  # noqa: E402


def main(argv=None, root: Path = spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cs = spec.load_cell(args.workload, root)
    sys.path.insert(0, str(root / "src"))
    run.use_compile_cache(root)
    devices, peaks = run.look_for_chip(cs["cell"]["chips"], root)
    lower, upper = {}, {}
    for seed in args.seeds:
        out = run.run_cell(cs, seed, args.seconds, False, devices, peaks,
                           control=True)
        for k, v in out["checks"].items():
            lower[k] = max(lower.get(k, 0), v["value"])
        for k, v in out["control"].items():
            upper[k] = min(upper.get(k, v["value"]), v["value"])
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": out["checks"],
                          "control": out["control"],
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
