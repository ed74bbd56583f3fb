"""Time the device tier's segment reduction both ways, to set
``parallel.DENSE_REDUCE_MAX_GROUPS``.

    PYTHONPATH=src python scripts/segment_reduce_crossover.py [--rows 65536]

For each group count and lane count, one batch of float64 values is
reduced by a uniform random group id, once by the dense masked reduction
and once by XLA's scatter (``jax.ops.segment_*``), each in its own jitted
program.  Each program runs ``--calls`` times back to back under the JAX
profiler; the device time per call is its ``XLA Modules`` events' total
over the calls, and the host time per call is the wall time of the calls
to the last result.  Prints one JSON line per case and a table.  Needs an
accelerator: the times of a CPU run say nothing about the chip.
"""

from __future__ import annotations

import argparse
import glob
import json
import tempfile
import time

import numpy as np

from repro.core import parallel as par

jax = par.jax

GROUPS = (1, 12, 64, 256, 1024, 4096)
CASES = [("sum", 3), ("sum", 15), ("min", 1)]


def program(op: str, path: str, n_groups: int, lanes: int):
    """A jitted reduction taking the given path whatever the constant."""
    def fn(values, gid):
        saved = par.DENSE_REDUCE_MAX_GROUPS
        par.DENSE_REDUCE_MAX_GROUPS = n_groups if path == "dense" else 0
        try:
            return par._segment_reduce(op, values, gid, n_groups)
        finally:
            par.DENSE_REDUCE_MAX_GROUPS = saved
    fn.__name__ = f"seg_{path}_{op}_g{n_groups}_k{lanes}"
    return jax.jit(fn)


def module_seconds(log_dir: str) -> dict:
    """Device seconds per jitted program, summed over its executions."""
    from jax.profiler import ProfileData
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                name = e.name.split("(")[0]
                out[name] = out.get(name, 0.0) + e.duration_ns * 1e-9
        break                                   # the first device
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--calls", type=int, default=30)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit("no accelerator: CPU times say nothing")
    rng = np.random.default_rng(0)
    runs = []
    for op, lanes in CASES:
        shape = (args.rows, lanes) if lanes > 1 else (args.rows,)
        values = jax.device_put(rng.uniform(1.0, 1e5, shape))
        for g in GROUPS:
            gid = jax.device_put(
                rng.integers(0, g, args.rows).astype(np.int32))
            for path in ("dense", "scatter"):
                fn = program(op, path, g, lanes)
                try:
                    fn(values, gid).block_until_ready()     # compile
                except Exception as e:                      # noqa: BLE001
                    runs.append((op, lanes, g, path, None, None,
                                 f"{type(e).__name__}: {e}"[:200]))
                    continue
                runs.append((op, lanes, g, path, fn, (values, gid), None))
    with tempfile.TemporaryDirectory() as log_dir:
        host = {}
        jax.profiler.start_trace(log_dir)
        try:
            for op, lanes, g, path, fn, fargs, err in runs:
                if fn is None:
                    continue
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = fn(*fargs)
                out.block_until_ready()
                host[fn.__name__] = (time.perf_counter() - t0) / args.calls
        finally:
            jax.profiler.stop_trace()
        device = module_seconds(log_dir)
    rows = []
    for op, lanes, g, path, fn, fargs, err in runs:
        rec = {"op": op, "lanes": lanes, "groups": g, "path": path,
               "rows": args.rows, "device": dev.device_kind}
        if err is not None:
            rec["error"] = err
        else:
            name = f"jit_{fn.__name__}"
            rec["device_us"] = device.get(name, float("nan")) \
                / args.calls * 1e6
            rec["host_us"] = host[fn.__name__] * 1e6
        rows.append(rec)
        print(json.dumps(rec))
    print("microseconds per call: device dense, scatter; host dense, "
          "scatter")
    nan = float("nan")
    for op, lanes in CASES:
        for g in GROUPS:
            t = {(r["path"], k): r.get(k, nan) for r in rows
                 if (r["op"], r["lanes"], r["groups"]) == (op, lanes, g)
                 for k in ("device_us", "host_us")}
            cells = [t[p, k] for k in ("device_us", "host_us")
                     for p in ("dense", "scatter")]
            print(f"{op:>4} K={lanes:<3} groups={g:<5}"
                  + "".join(f"{c:>12.2f}" for c in cells))

if __name__ == "__main__":
    main()
