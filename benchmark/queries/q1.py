"""TPC-H Q1 (pricing summary report), validation literals: the plain
reference (pandas, exact integer money) and the least bytes the device
must read. From chip_smoke.py::ref_q1 (PR 25)."""

from reference import days, frame, money, table_rows

TABLES = ("lineitem",)     # what the FROM names: the rows of rows_per_s
ORDERED = True             # ORDER BY on the (unique) group keys


def reference(path):
    li = frame(path, "lineitem", [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"])
    li = li[li.l_shipdate <= days(1998, 12, 1) - 90]
    li = li.assign(disc_price=li.l_extendedprice * (100 - li.l_discount))
    li = li.assign(charge=li.disc_price * (100 + li.l_tax), n=1)
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=True)[[
        "l_quantity", "l_extendedprice", "disc_price", "charge",
        "l_discount", "n"]].sum()
    out = []
    for (flag, status), r in g.iterrows():
        n = int(r.n)
        out.append((str(flag), str(status),
                    money(r.l_quantity, 2), money(r.l_extendedprice, 2),
                    money(r.disc_price, 4), money(r.charge, 6),
                    int(r.l_quantity) / 100 / n,
                    int(r.l_extendedprice) / 100 / n,
                    int(r.l_discount) / 100 / n, n))
    return out


def hbm_bytes(path):
    """The filter is not pushed (date - interval), so the whole table is
    resident and read: four decimal(12,2) columns as int64, the ship date
    as int32 and two dictionary codes as int32, 44 bytes a row."""
    return table_rows(path, "lineitem") * (4 * 8 + 4 + 2 * 4)
