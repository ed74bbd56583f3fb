"""CPU rehearsal of every cell: a run at a small fraction of its scale factor
and device budget, untraced and traced, with the look for a chip skipped; each answer must agree with the
template's reference.

    JAX_PLATFORMS=cpu python3 bench/checks/rehearse.py [--scale 0.01] [--seconds 2]

Device numbers from such a run are CPU numbers and are never reported as
the chip's.  Exits non-zero when a run is not correct.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--cells", nargs="*", default=None)
    args = ap.parse_args(argv)
    from bench import run
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = args.cells or [w["name"] for w in spec["workloads"]]
    bad = 0
    for name in names:
        cell = run.load_cell(name)
        for trace in (False, True):
            out = run.run_cell(cell, args.seed, args.seconds, trace,
                               scale=args.scale, require_tpu=False)
            diag = out.pop("diagnostics")
            print(name, f"trace={int(trace)}", json.dumps(out), diag,
                  flush=True)
            if not out["correct"] or out["failed"]:
                bad += 1
    return 1 if bad else 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
