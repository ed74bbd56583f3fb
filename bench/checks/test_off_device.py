"""A run that cannot measure the device ends before its window, and the
command exits 3 with no result line.

Q3 at 1% of SF 1 runs on the device join tier on the CPU (as in
``test_faults.test_two_streams_of_q3_are_correct``); each case below
pushes its one warm-up query off that tier.
"""

import json

import pytest

from bench import run
from bench.datagen import tpch as gen
from bench.trace import Summary
from repro.core import physplan


def _q3_cell():
    return run.Cell("q3-off-device", 1,
                    {"scale_factor": 1,
                     "tables": ["lineitem", "orders", "customer"],
                     "device_budget": 8 << 30},
                    {"loop": "closed", "streams": 1, "entry": "sql",
                     "mix": ["q3"]}, [], [])


@pytest.fixture
def no_window(monkeypatch):
    """Fails the test if the run reaches its window."""
    def closed_loop(*a, **kw):
        raise AssertionError("a window query was attempted")
    monkeypatch.setattr(run, "closed_loop", closed_loop)


def _run_q3(seed):
    return run.run_cell(_q3_cell(), seed, 0.5, False, scale=0.01,
                        require_tpu=False)


def test_host_plan_ends_the_warm_up(monkeypatch, no_window):
    """Order keys whose span exceeds the device join's domain plan Q3 on
    the host: the warm-up query reports no device tier."""
    monkeypatch.setattr(physplan, "MAX_DEVICE_JOIN_DOMAIN", 1 << 10)
    with pytest.raises(run.OffDevice, match=r"warm-up q3 .*tier ''"):
        _run_q3(2**31 + 21)


def test_device_fallback_ends_the_warm_up(monkeypatch, no_window):
    """Duplicate build keys make the device join fall back to the host at
    run time: the warm-up query records ``device_fallback``."""
    generate = gen.generate

    def duplicate_orders(sf, seed, tables):
        """Every odd order a copy of the even one before it, so that some
        duplicate passes Q3's filters on the build side."""
        data = generate(sf, seed, tables)
        for col in ("o_orderkey", "o_custkey", "o_orderdate"):
            a = data["orders"][col].data
            a[1::2] = a[:-1:2]
        return data
    monkeypatch.setattr(gen, "generate", duplicate_orders)
    with pytest.raises(run.OffDevice,
                       match=r"warm-up q3 .*_DeviceJoinFallback"):
        _run_q3(2**31 + 22)


def test_main_exits_3_with_no_result(monkeypatch, capsys):
    def off_device(*a, **kw):
        raise run.OffDevice("warm-up q3 left the device tier")
    monkeypatch.setattr(run, "run_cell", off_device)
    # main sets the cache directory for its process; undone after the test
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    cell = json.loads((run.ROOT / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 23),
                   "--seconds", "1", "--trace", "1"])
    out = capsys.readouterr()
    assert rc == 3
    assert out.out == ""
    assert out.err == "bench: warm-up q3 left the device tier\n"


def test_traced_chip_run_needs_a_device_op():
    with pytest.raises(run.OffDevice):
        run.require_device_ops(None, require_tpu=True)
    run.require_device_ops(None, require_tpu=False)     # the CPU's trace
    run.require_device_ops(Summary(1.0, 0.5, 10.0, 1), require_tpu=True)
