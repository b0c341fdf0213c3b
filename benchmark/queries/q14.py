"""TPC-H Q14 (promotion effect), validation literals. New in PR 25, in
the style of chip_smoke.py::ref_q6."""

from reference import days, frame, table_rows

TABLES = ("lineitem", "part")


def _month(path):
    li = frame(path, "lineitem", ["l_partkey", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    return li[(li.l_shipdate >= days(1995, 9, 1))
              & (li.l_shipdate < days(1995, 10, 1))]


def reference(path):
    part = frame(path, "part", ["p_partkey", "p_type"])
    j = _month(path).merge(part, left_on="l_partkey", right_on="p_partkey")
    revenue = j.l_extendedprice * (100 - j.l_discount)
    promo = int(revenue[j.p_type.str.startswith("PROMO")].sum())
    return [(100.0 * promo / int(revenue.sum()),)]


def hbm_bytes(path):
    """Both date bounds are pushed, so one month of lineitem is resident
    (part key and two decimals as int64, ship date as int32) beside all
    of part's key (int64) and type code (int32)."""
    return (len(_month(path)) * (3 * 8 + 4)
            + table_rows(path, "part") * (8 + 4))
