"""TPC-H Q3 (shipping priority, clause 2.4.3) with the validation literals
SEGMENT = 'BUILDING' and DATE = 1995-03-15: the spec's text
(q3_shipping.sql, the same as q3.sql), its plain reference, and the bytes
its scans and its two joins must move. A file of its own beside q3.py,
which no cell runs and which stays as it is."""

import datetime

from reference import EPOCH, days, frame, money

TABLES = ("customer", "orders", "lineitem")
#: the top 10 is compared in the order of ORDER BY revenue desc, o_orderdate
ORDERED = True
CUT = days(1995, 3, 15)


def _refuse_a_program_that_cannot_finish():
    """The one thing this file asks of the program, and not for its
    answer: a program that runs the first execution of a join operation
    by operation compiles each of its jax operations as a program of its
    own, the XLA sorts among them for tens of seconds apiece on the v5e
    (ROADMAP A2), and at SF10 its first execution of this query does not
    end inside a run's 1,200 s (on the v5e, from an empty compile cache,
    it was still compiling the first join's programs 1,095 s into that
    execution when the run's time ran out). It is refused here, at once and with a
    non-zero exit code, so that a check measures the cell on the programs
    that can run it. Such a program is known by the one name this cell's
    own yardstick holds still: the ``join.size`` span that ``join_size_s``
    reads came with the compiled sizing of a join, so no program has the
    one without the other, and nothing a refactor may rename is looked
    at (as queries/q15_revenue.py does)."""
    from spark_tpu import trace

    if "join.size" not in trace.SPAN_NAMES:
        raise SystemExit(
            "benchmark: q3_shipping needs a program that sizes a join's "
            "first execution in compiled programs (it records the "
            "join.size span this cell's metrics read): operation by "
            "operation it would not end inside a run at SF10")


_refuse_a_program_that_cannot_finish()


def _sides(path):
    """The rows each table keeps behind the query's filters."""
    cust = frame(path, "customer", ["c_custkey", "c_mktsegment"])
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = frame(path, "orders", ["o_orderkey", "o_custkey",
                                    "o_orderdate", "o_shippriority"])
    orders = orders[orders.o_orderdate < CUT]
    li = frame(path, "lineitem", ["l_orderkey", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    li = li[li.l_shipdate > CUT]
    return cust, orders, li


def _joined(path):
    cust, orders, li = _sides(path)
    j1 = orders.merge(cust, left_on="o_custkey", right_on="c_custkey")
    return cust, orders, li, j1, li.merge(j1, left_on="l_orderkey",
                                          right_on="o_orderkey")


def _groups(j):
    """Revenue by (l_orderkey, o_orderdate, o_shippriority): exact
    integers in 1e-4 units."""
    j = j.assign(revenue=j.l_extendedprice * (100 - j.l_discount))
    return j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                     as_index=False).revenue.sum()


def rows(groups):
    """Groups as the engine returns them: Decimal revenue of scale 4,
    dates as dates."""
    return [(int(r.l_orderkey), money(r.revenue, 4),
             EPOCH + datetime.timedelta(days=int(r.o_orderdate)),
             int(r.o_shippriority)) for r in groups.itertuples()]


def reference(path):
    g = _groups(_joined(path)[-1])
    return rows(g.sort_values(["revenue", "o_orderdate"],
                              ascending=[False, True]).head(10))


def hbm_bytes(path):
    """The resident columns read once: the filters on c_mktsegment,
    o_orderdate and l_shipdate are pushed into the scans, so the device
    holds c_custkey of the segment's customers, o_orderkey, o_custkey,
    o_orderdate and o_shippriority of the orders before the date, and
    l_orderkey, l_extendedprice and l_discount of the lines after it,
    each as the int32 it fits."""
    cust, orders, li = _sides(path)
    return len(cust) * 4 + len(orders) * 16 + len(li) * 12


def join_bytes(path):
    """What the query's two joins must move, each read once and each
    result written once, by the data and not by the program's
    capacities: customer with orders reads the customers' key and the
    orders' key and three columns it carries, and writes those three for
    every order of the segment; that with lineitem reads them again and
    the lines' key and two decimals, and writes five columns a joined
    line (4 B each, as the device holds them)."""
    cust, orders, li, j1, j2 = _joined(path)
    return (len(cust) * 4 + len(orders) * 16 + len(j1) * 12
            + len(j1) * 12 + len(li) * 12 + len(j2) * 20)
