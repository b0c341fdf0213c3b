"""TPC-H Q6 (forecasting revenue change), validation literals. From
chip_smoke.py::ref_q6 (PR 25)."""

from reference import days, frame, money

TABLES = ("lineitem",)


def _filtered(path):
    li = frame(path, "lineitem", ["l_extendedprice", "l_discount",
                                  "l_quantity", "l_shipdate"])
    return li[(li.l_shipdate >= days(1994, 1, 1))
              & (li.l_shipdate < days(1995, 1, 1))
              & (li.l_discount >= 5) & (li.l_discount <= 7)
              & (li.l_quantity < 2400)]


def reference(path):
    li = _filtered(path)
    return [(money((li.l_extendedprice * li.l_discount).sum(), 4),)]


def hbm_bytes(path):
    """Every conjunct is column-versus-literal and is pushed into the
    host's parquet read, so what is resident and read is the filtered
    rows: three decimals as int64 and the ship date as int32."""
    return len(_filtered(path)) * (3 * 8 + 4)
