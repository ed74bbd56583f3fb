"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the engine's device tier, the rest of the run is
the harness's own (with its look for a chip skipped), at a small fraction
of each cell's scale on the CPU:

* ``unchanged``: the batch and probe steps return their state unchanged;
* ``half_batch``: every batch's second half of rows is left out;
* ``altered``: one number of each answer is changed by one part in a
  million where the executor produces it.

The exchange between chips is not a fault these one-chip cells can have.
"""

import contextlib
import json

import jax.numpy as jnp
import pytest

from bench import run
from repro.core import parallel

# every cell of BENCHMARK.json
CELLS = [w["name"] for w in
         json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@contextlib.contextmanager
def patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    parallel._STEP_CACHE.clear()
    try:
        yield
    finally:
        setattr(obj, name, old)
        parallel._STEP_CACHE.clear()


def _unchanged(build):
    def wrapped(*a, **kw):
        init, _ = build(*a, **kw)
        return init, lambda carry, *args: carry
    return wrapped


def _half_batch(mask_gid):
    def wrapped(spec, meta, valid, arrays):
        keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
        return mask_gid(spec, meta, valid & keep, arrays)
    return wrapped


def _altered(execute):
    def wrapped(self, plan, do_optimize=True):
        table = execute(self, plan, do_optimize=do_optimize)
        for col in table.columns.values():
            if col.data.dtype.kind == "f" and len(col.data):
                col.data = col.data.copy()
                col.data[0] *= 1 + 1e-6
                break
        return table
    return wrapped


@contextlib.contextmanager
def fault(name):
    with contextlib.ExitStack() as stack:
        if name == "unchanged":
            for build in ("build_batch_step", "build_join_probe_step"):
                stack.enter_context(patched(
                    parallel, build, _unchanged(getattr(parallel, build))))
        elif name == "half_batch":
            stack.enter_context(patched(
                parallel, "_fragment_mask_gid",
                _half_batch(parallel._fragment_mask_gid)))
        else:
            stack.enter_context(patched(
                parallel.ParallelExecutor, "execute",
                _altered(parallel.ParallelExecutor.execute)))
        yield


def _run(cell):
    return run.run_cell(run.load_cell(cell), 2**31 + 3, 0.5, False,
                        scale=0.01, require_tpu=False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] and out["failed"] == 0, out["checks"]


def test_two_streams_of_q3_are_correct():
    """Two closed-loop clients on the Q3 template, which no cell runs yet
    (PERF.md, Open questions): at 1% of SF 1 its orders keys fit the
    device join."""
    cell = run.Cell("q3-two-streams", 1,
                    {"scale_factor": 1,
                     "tables": ["lineitem", "orders", "customer"],
                     "device_budget": 8 << 30},
                    {"loop": "closed", "streams": 2, "entry": "sql",
                     "mix": ["q3", "q3"]}, [], [])
    out = run.run_cell(cell, 2**31 + 4, 0.5, False, scale=0.01,
                       require_tpu=False)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["attempted"] >= 2


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", ["unchanged", "half_batch", "altered"])
def test_fault_is_caught(cell, name):
    with fault(name):
        out = _run(cell)
    assert not out["correct"], out["checks"]
