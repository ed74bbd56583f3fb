"""The device steps' group merge (``parallel._segment_reduce``).

Contracts under test:

* the dense masked reduction and XLA's scatter agree for sum, count, min
  and max, for group domains at, under and over the crossover constant,
  with NULL values, fully masked rows and empty groups: counts, min and
  max to the bit, sums to ``rtol=1e-12``; an empty group holds the
  identity (0, +inf, -inf);
* Q1's and Q6's batch steps lower with no scatter; a group domain over
  the constant still lowers to one;
* ``ExecStats.dense_reduce_steps`` counts the batch steps whose program
  took the dense path: every step of Q1 and Q6, none for a group domain
  over the constant.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import parallel as par
from repro.core import startup
from repro.core.physplan import plan_physical
from repro.data import tpch
from repro.data.tpch_queries import ALL_QUERIES

jax = par.jax
jnp = par.jnp

C = par.DENSE_REDUCE_MAX_GROUPS
ROWS = 4096


def _inputs(n_groups: int, masked: float, seed: int):
    """One batch as ``_fragment_partials`` sees it: a row mask (padding
    and filtered rows), NULL values, and group ids that leave every odd
    group empty (all groups are used when there is one)."""
    rng = np.random.default_rng(seed)
    valid = np.ones(ROWS, dtype=bool)
    valid[-100:] = False                               # batch padding
    mask = valid & (rng.random(ROWS) >= masked)
    null = rng.random(ROWS) < 0.1
    ok = mask & ~null
    f = rng.uniform(-1e4, 1e6, ROWS)
    if n_groups == 1:
        gid = np.zeros(ROWS, dtype=np.int32)
    else:
        gid = (2 * rng.integers(0, (n_groups + 1) // 2, ROWS)).astype(
            np.int32)
    return mask, ok, f, gid


def _values(op: str, mask, ok, f):
    if op == "sum":          # cnt_star, count, sum: the stacked sum lanes
        return np.stack([mask.astype(np.float64), ok.astype(np.float64),
                         np.where(ok, f, 0.0)], axis=1)
    return np.where(ok, f, np.inf if op == "min" else -np.inf)


def _reduce(op, values, gid, n_groups, dense: bool, monkeypatch):
    monkeypatch.setattr(par, "DENSE_REDUCE_MAX_GROUPS",
                        n_groups if dense else 0)
    fn = jax.jit(lambda v, g: par._segment_reduce(op, v, g, n_groups))
    return np.asarray(fn(jnp.asarray(values), jnp.asarray(gid)))


def _reference(op, values, gid, n_groups):
    shape = (n_groups,) + values.shape[1:]
    if op == "sum":
        out = np.zeros(shape)
        np.add.at(out, gid, values)
    elif op == "min":
        out = np.full(shape, np.inf)
        np.minimum.at(out, gid, values)
    else:
        out = np.full(shape, -np.inf)
        np.maximum.at(out, gid, values)
    return out


@pytest.mark.parametrize("masked", [0.3, 1.0])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("n_groups", [1, 12, C, C + 1])
def test_dense_and_scatter_paths_agree(n_groups, op, masked, monkeypatch):
    mask, ok, f, gid = _inputs(n_groups, masked, seed=n_groups)
    values = _values(op, mask, ok, f)
    dense = _reduce(op, values, gid, n_groups, True, monkeypatch)
    scatter = _reduce(op, values, gid, n_groups, False, monkeypatch)
    ref = _reference(op, values, gid, n_groups)
    assert dense.shape == scatter.shape == ref.shape
    if op == "sum":
        np.testing.assert_array_equal(dense[:, :2], scatter[:, :2])
        np.testing.assert_array_equal(dense[:, :2], ref[:, :2])
        np.testing.assert_allclose(dense[:, 2], scatter[:, 2], rtol=1e-12)
        np.testing.assert_allclose(dense[:, 2], ref[:, 2], rtol=1e-12)
    else:
        np.testing.assert_array_equal(dense, scatter)
        np.testing.assert_array_equal(dense, ref)
    identity = {"sum": 0.0, "min": np.inf, "max": -np.inf}[op]
    empty = np.ones(n_groups, dtype=bool)
    empty[gid[ok]] = False
    if n_groups > 1:
        assert empty[1::2].all()
    if op == "sum":
        assert (dense[empty, 1:] == identity).all()
    else:
        assert (dense[empty] == identity).all()


@pytest.fixture(scope="module")
def sf_db():
    db = startup()
    tpch.load_into(db, 0.01, tables=["lineitem"])
    yield db
    db.shutdown()


def _cpu_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _lowered_step(db, spec):
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _cpu_mesh()
    t = db.catalog.table(spec.table)
    meta = {c: (t.column(c).dbtype, t.column(c).heap, t.column(c).scale)
            for c in spec.columns}
    _init, step = par.build_batch_step(spec, meta, mesh)
    rows = NamedSharding(mesh, P("data"))
    batch = [jax.ShapeDtypeStruct((8192,), np.bool_, sharding=rows)] + [
        jax.ShapeDtypeStruct((8192,), t.column(c).data.dtype, sharding=rows)
        for c in spec.columns]
    carry = jax.ShapeDtypeStruct(
        (spec.n_groups, len(par.partial_layout(spec).kinds)), np.float64,
        sharding=NamedSharding(mesh, P()))
    return step.lower(carry, *batch).as_text()


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_scan_agg_steps_lower_without_scatter(sf_db, q):
    spec = plan_physical(ALL_QUERIES[q](sf_db).plan, sf_db,
                         distributed=True, mesh=_cpu_mesh()).scan_agg
    assert spec.n_groups <= C
    assert "scatter" not in _lowered_step(sf_db, spec)
    wide = dataclasses.replace(spec, n_groups=C + 1)
    assert "scatter" in _lowered_step(sf_db, wide)


@pytest.fixture(scope="module")
def dev_db():
    db = startup(device_budget=64 << 20, device_batch_rows=8192)
    tpch.load_into(db, 0.01, tables=["lineitem"])
    rng = np.random.default_rng(5)
    n = 40_000
    k = rng.integers(0, C + 1, n).astype(np.int32)
    k[:C + 1] = np.arange(C + 1)                   # every key present
    db.create_table("wide", {"k": k, "v": rng.uniform(-50.0, 50.0, n)})
    yield db
    db.shutdown()


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_dense_reduce_steps_count_every_step(dev_db, q):
    ALL_QUERIES[q](dev_db).execute(distributed=True)
    st = dev_db.last_stats
    assert st.device_tier == "resident" and not st.device_fallback
    assert st.span_n["step"] > 0
    assert st.dense_reduce_steps == st.span_n["step"]


def test_dense_reduce_steps_zero_over_the_constant(dev_db):
    """C + 1 groups: the scatter path, counted as no dense step, with the
    host executor's answer."""
    def q():
        return (dev_db.scan("wide").group_by("k")
                .agg(s=("sum", "v"), lo=("min", "v"), hi=("max", "v"),
                     n=("count", None)))

    dev = q().execute(distributed=True).to_pydict()
    st = dev_db.last_stats
    assert st.device_tier == "resident" and not st.device_fallback
    assert st.span_n["step"] > 0 and st.dense_reduce_steps == 0
    host = q().execute().to_pydict()
    assert len(dev["k"]) == C + 1
    np.testing.assert_array_equal(dev["k"], host["k"])
    for c in ("lo", "hi", "n"):
        np.testing.assert_array_equal(dev[c], host[c])
    np.testing.assert_allclose(dev["s"], host["s"], rtol=1e-9)
