"""TPC-H Q3 (shipping priority), validation literals. From
chip_smoke.py::ref_q3 (PR 25). No cell runs it yet (PERF.md, Open
questions): its first run compiles 27 sort programs."""

import datetime

from reference import EPOCH, days, frame, money

TABLES = ("customer", "orders", "lineitem")


def reference(path):
    cut = days(1995, 3, 15)
    cust = frame(path, "customer", ["c_custkey", "c_mktsegment"])
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = frame(path, "orders", ["o_orderkey", "o_custkey",
                                    "o_orderdate", "o_shippriority"])
    orders = orders[orders.o_orderdate < cut]
    li = frame(path, "lineitem", ["l_orderkey", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    li = li[li.l_shipdate > cut]
    j = li.merge(orders.merge(cust, left_on="o_custkey",
                              right_on="c_custkey"),
                 left_on="l_orderkey", right_on="o_orderkey")
    j = j.assign(revenue=j.l_extendedprice * (100 - j.l_discount))
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).revenue.sum()
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10)
    return [(int(r.l_orderkey), money(r.revenue, 4),
             EPOCH + datetime.timedelta(days=int(r.o_orderdate)),
             int(r.o_shippriority)) for r in g.itertuples()]
