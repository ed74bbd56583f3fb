"""The float32 control fails every template's comparison, and the float64
reference passes it against itself with nothing to spare."""

import json

import numpy as np
import pytest

from bench import run
from bench.checks import control
from bench.datagen import tpch as gen

# every cell of BENCHMARK.json
CELLS = [w["name"] for w in
         json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**32 + 17])
def test_control_fails_a_limit(cell, seed):
    c = run.load_cell(cell)
    got = control.readings(c, seed, scale=0.01)
    limits = {}
    for t in c.traffic["mix"]:
        limits.update(run.template(t).LIMITS)
    assert any(v > limits[k] for k, v in got.items()), got


@pytest.mark.parametrize("name", ["q1", "q3", "q6"])
def test_reference_agrees_with_itself(name):
    data = gen.generate(0.01, 7, ["lineitem", "orders", "customer"])
    mod = run.template(name)
    want = mod.reference(data)
    assert all(v == 0 for v in mod.compare(want, want).values())
    assert mod.logical_bytes(data) > 0
    float_cols = [k for k, v in want.items()
                  if isinstance(v, np.ndarray) and v.dtype.kind == "f"]
    assert float_cols
