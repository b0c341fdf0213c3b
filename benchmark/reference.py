"""What the plain references share: parquet -> pandas frames with exact
integer money, and the comparison that decides ``correct``.

Copied from chip_smoke.py (``_frame``, ``_days``) and
spark_tpu/tpch/oracle.py (``assert_rows_match``) as of PR 25; it imports
nothing from spark_tpu. Money is carried as exact integer hundredths
(every decimal column of the generator has scale 2), so a sum of
price * (1 - discount) is an exact integer in 1e-4 units and only a final
division is floating point.
"""

from __future__ import annotations

import datetime
import decimal
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def days(year: int, month: int, day: int) -> int:
    return (datetime.date(year, month, day) - EPOCH).days


def money(units: int, scale: int) -> decimal.Decimal:
    """An exact integer sum in 10**-scale units as the Decimal the engine
    must return."""
    return decimal.Decimal(int(units)).scaleb(-scale)


def _unscaled(col) -> np.ndarray:
    """A decimal128 column's unscaled values as int64, read from the raw
    16-byte little-endian buffer (the inverse of the generator's
    ``_decimal_col``; exact, and 25x faster than a cast through float64)."""
    arr = col.combine_chunks() if hasattr(col, "combine_chunks") else col
    if arr.null_count:
        raise ValueError("a decimal column with nulls")
    limbs = np.frombuffer(arr.buffers()[1], dtype=np.int64)[
        2 * arr.offset:2 * (arr.offset + len(arr))]
    low, high = limbs[0::2], limbs[1::2]
    if not np.array_equal(high, np.where(low < 0, -1, 0)):
        raise ValueError("a decimal value does not fit 64 bits")
    return low.copy()


def frame(path: str, table: str, columns: Sequence[str]):
    """One table's columns as a pandas frame: decimals as int64
    hundredths, dates as int32 days since the epoch."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    t = pq.read_table(os.path.join(path, f"{table}.parquet"),
                      columns=list(columns))
    cols = {}
    for name, col in zip(t.column_names, t.columns):
        if pa.types.is_decimal(col.type):
            if col.type.scale != 2:
                raise ValueError(f"{table}.{name}: {col.type}, not scale 2")
            cols[name] = _unscaled(col)
        elif pa.types.is_date32(col.type):
            cols[name] = col.cast(pa.int32()).to_numpy()
        elif pa.types.is_dictionary(col.type):
            cols[name] = col.cast(col.type.value_type).to_pandas()
        else:
            cols[name] = col.to_pandas()
    return pd.DataFrame(cols)


def table_rows(path: str, table: str) -> int:
    import pyarrow.parquet as pq

    return pq.read_metadata(os.path.join(path, f"{table}.parquet")).num_rows


# ---- the comparison that decides `correct` ----------------------------------

REL = 1e-6   # only for values of which one side is a float (final divisions)


def _key(row: Tuple) -> Tuple:
    return tuple((v is None, str(v)) for v in row)


def rows_differ(got: List[Tuple], want: List[Tuple]) -> Optional[str]:
    """None if the rows agree, else the first difference. Integers,
    strings, dates and a Decimal against a Decimal are compared exactly;
    where either side is a float the values may differ by REL (relative).
    A query with ORDER BY is compared in order, so the caller sorts both
    sides only where the text leaves the order open."""
    if len(got) != len(want):
        return f"{len(got)} rows, the reference has {len(want)}"
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: {len(g)} columns, the reference has {len(w)}"
        for j, (a, b) in enumerate(zip(g, w)):
            if isinstance(a, float) or isinstance(b, float):
                if a is None or b is None:
                    return f"row {i} col {j}: {a!r} != {b!r}"
                denom = max(abs(float(a)), abs(float(b)), 1.0)
                if abs(float(a) - float(b)) / denom > REL:
                    return f"row {i} col {j}: {a!r} != {b!r}"
            elif a != b:
                return f"row {i} col {j}: {a!r} != {b!r}"
    return None


def sorted_rows(rows: List[Tuple]) -> List[Tuple]:
    return sorted(rows, key=_key)
