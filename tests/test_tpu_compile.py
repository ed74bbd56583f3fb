"""The device tier's jitted steps compile for a TPU v5e.

Each test lowers one step of the served path for a described (not
attached) v5e chip at the shapes TPC-H SF 1 gives it: 65,536-row batches
and SF 1 key domains, with the specs the physical planner derives for
Q1, Q6 and Q3.  What the chip's compiler refuses here fails without
spending chip time.  Nothing runs, so these tests say nothing about
results or speed.
"""

import dataclasses
import os

import numpy as np
import pytest

from repro.core import parallel as par
from repro.core import startup
from repro.core.physplan import DEVICE_BATCH_ROWS, plan_physical
from repro.data import tpch
from repro.data.tpch_queries import ALL_QUERIES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A one-device mesh on the described chip, with the persistent
    compile cache off: entries compiled for a described chip cannot be
    read back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield Mesh(np.array(topo.devices[:1]), ("data",))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def small_db():
    db = startup()
    tpch.load_into(db, 0.01, tables=["lineitem", "orders", "customer"])
    yield db
    db.shutdown()


def _phys(db, q):
    import jax
    from jax.sharding import Mesh
    cpu = Mesh(np.array(jax.devices()[:1]), ("data",))
    return plan_physical(ALL_QUERIES[q](db).plan, db, distributed=True,
                         mesh=cpu)


def _meta(db, table, cols):
    t = db.catalog.table(table)
    return {c: (t.column(c).dbtype, t.column(c).heap, t.column(c).scale)
            for c in cols}


def _batch(db, mesh, table, cols):
    """Shapes of one batch: the valid mask, then every column."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows = NamedSharding(mesh, P("data"))
    t = db.catalog.table(table)
    return [jax.ShapeDtypeStruct((DEVICE_BATCH_ROWS,), np.bool_,
                                 sharding=rows)] + [
        jax.ShapeDtypeStruct((DEVICE_BATCH_ROWS,), t.column(c).data.dtype,
                             sharding=rows) for c in cols]


def _matrix(mesh, rows, width):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.ShapeDtypeStruct((rows, width), np.float64,
                                sharding=NamedSharding(mesh, P()))


def _q3_sf1(db):
    """Q3's join spec with SF 1 key domains (dense keys from 0)."""
    js = _phys(db, "q3").join_agg
    builds = [dataclasses.replace(b, domain=(0.0, tpch.SF_ROWS[b.table]))
              for b in js.builds]
    n = tpch.SF_ROWS["orders"]
    return dataclasses.replace(js, builds=builds, key_domain=(0.0, n),
                               n_groups=n)


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_scan_agg_batch_step_compiles(one_chip, small_db, q):
    spec = _phys(small_db, q).scan_agg
    _init, step = par.build_batch_step(
        spec, _meta(small_db, spec.table, spec.columns), one_chip)
    carry = _matrix(one_chip, spec.n_groups,
                    len(par.partial_layout(spec).kinds))
    step.lower(carry, *_batch(small_db, one_chip, spec.table,
                              spec.columns)).compile()


def test_q3_join_build_step_compiles(one_chip, small_db):
    """The orders build at SF 1: a (1.5M, 3) matrix probed through the
    customer build's (150k, 1) presence matrix."""
    js = _q3_sf1(small_db)
    orders = next(b for b in js.builds if b.table == "orders")
    children = [js.builds[ci] for ci, _ in orders.probe_edges]
    _init, step = par.build_join_build_step(
        orders, _meta(small_db, "orders", orders.columns), one_chip,
        tuple(c.domain for c in children))
    btab = _matrix(one_chip, orders.domain[1], 1 + len(orders.payload))
    kids = [_matrix(one_chip, c.domain[1], 1 + len(c.payload))
            for c in children]
    step.lower(btab, *kids, *_batch(small_db, one_chip, "orders",
                                    orders.columns)).compile()


def test_q3_join_probe_step_compiles(one_chip, small_db):
    js = _q3_sf1(small_db)
    pspec = js.probe_spec()
    _init, step = par.build_join_probe_step(
        js, _meta(small_db, pspec.table, pspec.columns), one_chip)
    carry = _matrix(one_chip, pspec.n_groups,
                    len(par.partial_layout(pspec).kinds))
    edges = [_matrix(one_chip, js.builds[bi].domain[1],
                     1 + len(js.builds[bi].payload))
             for bi, _ in js.probe_edges]
    step.lower(carry, *edges, *_batch(small_db, one_chip, pspec.table,
                                      pspec.columns)).compile()
