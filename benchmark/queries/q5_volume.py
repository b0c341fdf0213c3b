"""TPC-H Q5 (local supplier volume, clause 2.4.5) with the validation
literals REGION = 'ASIA' and DATE = 1994-01-01: the spec's text
(q5_volume.sql, the same as q5.sql), its plain reference, and the bytes its
scans and its five joins must move. A file of its own beside q5.py, which
no cell runs and which stays as it is."""

from reference import days, frame, money

TABLES = ("customer", "orders", "lineitem", "supplier", "nation", "region")
#: the nations are compared in the order of ORDER BY revenue desc
ORDERED = True
START, END = days(1994, 1, 1), days(1995, 1, 1)


def _sides(path):
    """The rows each table keeps behind the query's filters, with the
    columns the query reads of it."""
    region = frame(path, "region", ["r_regionkey", "r_name"])
    region = region[region.r_name == "ASIA"][["r_regionkey"]]
    nation = frame(path, "nation", ["n_nationkey", "n_name", "n_regionkey"])
    supp = frame(path, "supplier", ["s_suppkey", "s_nationkey"])
    li = frame(path, "lineitem", ["l_orderkey", "l_suppkey",
                                  "l_extendedprice", "l_discount"])
    orders = frame(path, "orders", ["o_orderkey", "o_custkey",
                                    "o_orderdate"])
    orders = orders[(orders.o_orderdate >= START)
                    & (orders.o_orderdate < END)]
    cust = frame(path, "customer", ["c_custkey", "c_nationkey"])
    return region, nation, supp, li, orders, cust


def _joined(path):
    """The sides and the output of each of the five joins, in the order
    the query's joins take them: region, nation, supplier, lineitem,
    orders, customer; the last one on two keys."""
    region, nation, supp, li, orders, cust = sides = _sides(path)
    j1 = nation.merge(region, left_on="n_regionkey", right_on="r_regionkey")
    j2 = supp.merge(j1, left_on="s_nationkey", right_on="n_nationkey")
    j3 = li.merge(j2, left_on="l_suppkey", right_on="s_suppkey")
    j4 = j3.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    j5 = j4.merge(cust, left_on=["o_custkey", "s_nationkey"],
                  right_on=["c_custkey", "c_nationkey"])
    return sides, (j1, j2, j3, j4, j5)


def reference(path):
    """Revenue by nation, exact integers in 1e-4 units, as the Decimal of
    scale 4 the engine returns, largest first."""
    j = _joined(path)[1][-1]
    j = j.assign(revenue=j.l_extendedprice * (100 - j.l_discount))
    g = j.groupby("n_name", as_index=False).revenue.sum()
    g = g.sort_values("revenue", ascending=False)
    return [(str(r.n_name), money(r.revenue, 4)) for r in g.itertuples()]


def hbm_bytes(path):
    """The resident columns read once: the filters on r_name and
    o_orderdate are pushed into the scans, so the device holds r_regionkey
    of ASIA, n_nationkey, n_name and n_regionkey of the nations,
    s_suppkey and s_nationkey of every supplier, l_orderkey, l_suppkey,
    l_extendedprice and l_discount of every line, o_orderkey, o_custkey
    and o_orderdate of the orders of 1994, and c_custkey and c_nationkey
    of every customer, each as the int32 it fits (a string as its code)."""
    region, nation, supp, li, orders, cust = _sides(path)
    return 4 * (len(region) + 3 * len(nation) + 2 * len(supp)
                + 4 * len(li) + 3 * len(orders) + 2 * len(cust))


def join_bytes(path):
    """What the query's five joins must move, each side read once and each
    result written once, by the data and not by the program's capacities
    (4 B a column, as the device holds them): ASIA with the nations
    (region key; nation key, name, region key) writes the nation key and
    name; those with the suppliers (supplier and nation key) write the
    supplier key, nation key and name; those with every line (order and
    supplier key, two decimals) write the nation key, name, order key and
    two decimals a line; those with the orders of 1994 (order and customer
    key) write the nation key, name, two decimals and customer key; those
    with the customers (customer and nation key) write the name and two
    decimals of every line whose customer and supplier share a nation."""
    (region, nation, supp, li, orders, cust), (j1, j2, j3, j4, j5) = \
        _joined(path)
    return 4 * (len(region) + 3 * len(nation) + 2 * len(j1)
                + 2 * len(j1) + 2 * len(supp) + 3 * len(j2)
                + 3 * len(j2) + 4 * len(li) + 5 * len(j3)
                + 5 * len(j3) + 2 * len(orders) + 5 * len(j4)
                + 5 * len(j4) + 2 * len(cust) + 3 * len(j5))
