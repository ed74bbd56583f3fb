"""Zero-copy + lazy data exchange (paper §3.3, adapted per DESIGN.md §3).

Three transfer paths between the engine and the embedding analytical code:

* **zero_copy_view(col)** — a read-only numpy view over the engine's own
  packed buffer.  No bytes move; the read-only flag is the functional
  equivalent of the paper's mprotect write-trap, and ``copy_for_write``
  gives the copy-on-write escape hatch.
* **to_device(col)** — the engine's device tier handed to JAX; on the host
  platform this aliases through dlpack when bit-compatible (the zero-copy
  condition of §3.3), otherwise it is the one explicit conversion.
* **LazyFrame** — the lazy-conversion path (paper Fig. 4): a query result
  whose columns are *thunks*; decode work (dict decode, date decode, NULL
  rewrite) happens on first access per column, never for untouched columns.
  ``conversions`` counts materializations so tests/benchmarks can assert
  SELECT * + touch-one-column converts exactly one column.

Header forgery has no TPU-side analogue to forge (DESIGN.md §3): a
``jax.Array``/numpy view already separates the header object from the
buffer, so metadata prepending is free; the invariant we keep from the
paper is *O(1) transfer cost, independent of data size*: a zero-copy
transfer shares the column's buffer (tests/test_exchange.py).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .column import Column
from .table import Table
from .types import DBType, is_float


def zero_copy_view(col: Column) -> np.ndarray:
    """Read-only view of the packed storage array (no copy, O(1))."""
    v = np.asarray(col.data)
    view = v.view()
    view.flags.writeable = False
    return view


def copy_for_write(col: Column) -> np.ndarray:
    """Copy-on-write escape hatch: a private, writable copy."""
    return np.array(col.data, copy=True)


def is_zero_copy_eligible(col: Column) -> bool:
    """Bit-compatibility rule of §3.3: numeric fixed-width columns share
    their buffer; VARCHAR/DECIMAL/BOOL/DATE need decoding."""
    return col.dbtype in (DBType.INT32, DBType.INT64,
                          DBType.FLOAT32, DBType.FLOAT64) \
        and (is_float(col.dbtype) or not col.has_nulls())


def to_device(col: Column):
    """Engine column -> jax.Array (device tier). Cached on the column."""
    return col.device()


class LazyFrame:
    """Lazily-converted result set (paper's 'dummy arrays' + fault handler,
    restated as thunks)."""

    def __init__(self, table: Table):
        self._table = table
        self._cache: dict[str, np.ndarray] = {}
        self.conversions = 0
        self.zero_copies = 0

    @property
    def columns(self) -> list[str]:
        return list(self._table.schema.names)

    @property
    def num_rows(self) -> int:
        return self._table.num_rows

    def __getitem__(self, name: str) -> np.ndarray:
        if name not in self._cache:
            col = self._table.columns[name]
            if is_zero_copy_eligible(col):
                self._cache[name] = zero_copy_view(col)
                self.zero_copies += 1
            else:
                self._cache[name] = col.to_numpy()
                self.conversions += 1
        return self._cache[name]

    def touched(self) -> list[str]:
        return list(self._cache)


def export_table(table: Table, lazy: bool = True):
    """The dbReadTable path (paper Fig. 6): lazy by default."""
    if lazy:
        return LazyFrame(table)
    return table.to_pydict()


def import_arrays(name: str, data: dict[str, np.ndarray],
                  types: Optional[dict] = None) -> Table:
    """The dbWriteTable path (paper Fig. 5): bulk columnar ingest.  Numeric
    numpy arrays are adopted without copy (the engine stores the same
    buffer); only strings/objects are encoded."""
    return Table.from_dict(name, data, types)
