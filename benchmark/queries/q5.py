"""TPC-H Q5 (local supplier volume), validation literals. From
chip_smoke.py::ref_q5 (PR 25). No cell runs it yet (PERF.md, Open
questions): its first run compiles 30 sort programs."""

from reference import days, frame, money

TABLES = ("customer", "orders", "lineitem", "supplier", "nation", "region")


def reference(path):
    region = frame(path, "region", ["r_regionkey", "r_name"])
    nation = frame(path, "nation", ["n_nationkey", "n_name", "n_regionkey"])
    nation = nation.merge(region[region.r_name == "ASIA"],
                          left_on="n_regionkey", right_on="r_regionkey")
    supp = frame(path, "supplier", ["s_suppkey", "s_nationkey"])
    cust = frame(path, "customer", ["c_custkey", "c_nationkey"])
    orders = frame(path, "orders", ["o_orderkey", "o_custkey",
                                    "o_orderdate"])
    orders = orders[(orders.o_orderdate >= days(1994, 1, 1))
                    & (orders.o_orderdate < days(1995, 1, 1))]
    li = frame(path, "lineitem", ["l_orderkey", "l_suppkey",
                                  "l_extendedprice", "l_discount"])
    j = li.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(cust, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(supp, left_on=["l_suppkey", "c_nationkey"],
                right_on=["s_suppkey", "s_nationkey"])
    j = j.merge(nation, left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(revenue=j.l_extendedprice * (100 - j.l_discount))
    g = j.groupby("n_name", as_index=False).revenue.sum()
    g = g.sort_values("revenue", ascending=False)
    return [(str(r.n_name), money(r.revenue, 4)) for r in g.itertuples()]
