"""The control of the comparison that decides ``correct``: each template's
reference, put in the engine's place and computed in float32, the
precision below the float64 the configurations state.  Its numbers are
the upper readings the limits in ``bench/queries`` are set under; a
control that a limit would pass shows the comparison cannot fail.

    python3 bench/checks/control.py --workload <cell> --seeds 1 2 3 [--scale 1]

needs no accelerator, runs at the cell's own size by default, and prints
one JSON line per seed with each number beside its limit.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, seed: int, scale: float = 1.0) -> dict:
    """``{name: value}`` of the float32 control for every template of the
    cell's mix, against the float64 reference, on one seed's data."""
    from bench import run
    from bench.datagen import tpch as gen
    conf = cell.config
    data = gen.generate(conf["scale_factor"] * scale, seed % 2**63,
                        conf["tables"])
    out = {}
    for t in dict.fromkeys(cell.traffic["mix"]):
        mod = run.template(t)
        want = mod.reference(data)
        got = mod.reference(data, dtype=np.float32)
        out.update(mod.compare(got, want))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    from bench import run
    cell = run.load_cell(args.workload)
    limits = {}
    for t in cell.traffic["mix"]:
        limits.update(run.template(t).LIMITS)
    caught = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = readings(cell, seed, args.scale)
        fails = {k: v for k, v in got.items() if v > limits[k]}
        caught &= bool(fails)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "control": {k: [v, limits[k]]
                                      for k, v in got.items()},
                          "caught": sorted(fails)}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
