"""Vectorized TPC-H data generator (spec-shaped dbgen).

Produces the eight TPC-H tables as Arrow tables with the spec's schema,
key structure, value domains and the text patterns the 22 queries
predicate on (Brand#MN, container/type vocabularies, p_name words,
comment injections, phone country codes, date windows). Row counts and
distributions follow the TPC-H specification section 4.2; text is
simplified (random word sequences rather than the spec's grammar) except
where queries match on it. Reference peer: the dbgen tool invoked by
TPCHQuerySuite (reference: sql/core/.../TPCHQuerySuite.scala:26).
"""

from __future__ import annotations

import datetime
from typing import Dict, Optional

import numpy as np
import pyarrow as pa

EPOCH = datetime.date(1970, 1, 1)
START = (datetime.date(1992, 1, 1) - EPOCH).days      # o_orderdate low
END = (datetime.date(1998, 8, 2) - EPOCH).days        # o_orderdate high

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]

# (nation, region index) — spec Table 4.2.3
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]

TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]

P_NAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod",
    "green", "grey", "honeydew", "hot", "indian", "ivory", "khaki",
    "lace", "lavender", "lawn", "lemon", "light", "lime", "linen",
    "magenta", "maroon", "medium", "metallic", "midnight", "mint", "misty",
    "moccasin", "navajo", "navy", "olive", "orange", "orchid", "pale",
    "papaya", "peach", "peru", "pink", "plum", "powder", "puff", "purple",
    "red", "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
    "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]

_COMMENT_WORDS = np.array([
    "carefully", "quickly", "furiously", "slyly", "blithely", "deposits",
    "requests", "packages", "accounts", "instructions", "foxes", "ideas",
    "theodolites", "pinto", "beans", "asymptotes", "dependencies", "somas",
    "platelets", "sleep", "haggle", "nag", "wake", "cajole", "detect",
    "integrate", "boost", "among", "final", "ironic", "express", "regular",
    "bold", "even", "silent", "pending", "special", "unusual",
])


MONEY = pa.decimal128(12, 2)


def _decimal_col(unscaled: np.ndarray, typ=MONEY) -> pa.Array:
    from spark_tpu.columnar.arrow import decimal_from_unscaled

    return decimal_from_unscaled(unscaled, typ)


def _money(rng, n, lo, hi) -> pa.Array:
    """Money columns are DECIMAL(12,2) per the TPC-H spec (the engine
    executes them as exact scaled int64; reference: Decimal.scala)."""
    cents = rng.integers(round(lo * 100), round(hi * 100) + 1, n)
    return _decimal_col(cents)


def _words(rng, n: int, k: int) -> np.ndarray:
    """k-word random comment strings."""
    idx = rng.integers(0, len(_COMMENT_WORDS), (n, k))
    parts = _COMMENT_WORDS[idx]
    out = parts[:, 0]
    for j in range(1, k):
        out = np.char.add(np.char.add(out, " "), parts[:, j])
    return out


def _pick(rng, n, values) -> np.ndarray:
    return np.array(values)[rng.integers(0, len(values), n)]


# ---- dictionary-encoded column builders -------------------------------------
#
# Emitting pa.DictionaryArray (int32 indices + a small vocabulary)
# instead of materialized string arrays is the whole speedup: the old
# path built millions of numpy strings and then `list()`-converted them
# for pyarrow (~160 s at SF1). The engine dictionary-encodes strings on
# ingest anyway, so this also skips a conversion on the read side.


def _dict_col(indices: np.ndarray, vocab) -> pa.DictionaryArray:
    return pa.DictionaryArray.from_arrays(
        pa.array(indices.astype(np.int32), pa.int32()),
        pa.array(list(vocab), pa.string()))


def _pick_dict(rng, n, values) -> pa.DictionaryArray:
    return _dict_col(rng.integers(0, len(values), n), values)


def _words_dict(rng, n: int, k: int, pool: int = 4096,
                inject=None) -> pa.DictionaryArray:
    """Comment column as a dictionary over ``pool`` pre-built k-word
    strings. ``inject`` = (row_indices, strings) appends extra vocab
    entries and points those rows at them (q13/q16 pattern rows)."""
    pool = min(pool, max(64, n))
    vocab = list(_words(rng, pool, k))
    idx = rng.integers(0, pool, n)
    if inject is not None:
        rows, strings = inject
        strings = list(dict.fromkeys(strings))  # vocab must be unique
        if len(rows) and strings:
            base = len(vocab)
            vocab.extend(strings)
            idx[rows] = base + np.arange(len(rows)) % len(strings)
    return _dict_col(idx, vocab)


def _numbered(prefix: str, keys: np.ndarray) -> np.ndarray:
    """'Prefix#%09d' strings, vectorized (no Python format loop)."""
    return np.char.add(
        prefix, np.char.zfill(keys.astype(np.int64).astype(str), 9))


def _numbered_names(prefix: str, keys: np.ndarray) -> pa.Array:
    return pa.array(_numbered(prefix, keys))


def generate_tables(sf: float = 0.01,
                    seed: int = 20260729) -> Dict[str, pa.Table]:
    """All eight tables at scale factor ``sf`` (sf=1 is ~6M lineitems).
    In-RAM path for sf <= ~10; above that use write_parquet_streamed
    (SF100 lineitem alone would need ~80 GB of host arrays)."""
    rng = np.random.default_rng(seed)
    tables, ctx = _gen_static(sf, rng)
    n_ord = max(1, int(1_500_000 * sf))
    orders, lineitem = _gen_orders_slice(rng, 1, n_ord + 1, ctx)
    tables["orders"] = orders
    tables["lineitem"] = lineitem
    return tables


def _gen_static(sf: float, rng) -> tuple:
    """The six non-order tables plus the context the orders/lineitem
    generator needs (part retail prices, key cardinalities)."""
    tables: Dict[str, pa.Table] = {}

    # region / nation --------------------------------------------------------
    tables["region"] = pa.table({
        "r_regionkey": pa.array(np.arange(5), pa.int64()),
        "r_name": pa.array(REGIONS),
        "r_comment": pa.array(list(_words(rng, 5, 6))),
    })
    tables["nation"] = pa.table({
        "n_nationkey": pa.array(np.arange(25), pa.int64()),
        "n_name": pa.array([n for n, _ in NATIONS]),
        "n_regionkey": pa.array(np.array([r for _, r in NATIONS]),
                                pa.int64()),
        "n_comment": pa.array(list(_words(rng, 25, 8))),
    })

    # part --------------------------------------------------------------------
    n_part = max(1, int(200_000 * sf))
    pk = np.arange(1, n_part + 1)
    # p_name: 5-word strings from a pooled vocabulary (q9 predicates on
    # '%green%' — the pool keeps every color word's hit rate intact)
    name_pool = min(8192, max(64, n_part))
    wl = np.array(P_NAME_WORDS)
    nm = wl[rng.integers(0, len(wl), (name_pool, 5))]
    name_vocab = nm[:, 0]
    for j in range(1, 5):
        name_vocab = np.char.add(np.char.add(name_vocab, " "), nm[:, j])
    brand_m = rng.integers(1, 6, n_part)
    brand_n = rng.integers(1, 6, n_part)
    brand_vocab = [f"Brand#{m}{n}" for m in range(1, 6)
                   for n in range(1, 6)]
    type_vocab = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2
                  for c in TYPE_S3]
    cont_vocab = [f"{a} {b}" for a in CONTAINER_S1 for b in CONTAINER_S2]
    # spec: (90000 + ((partkey/10) mod 20001) + 100*(partkey mod 1000))/100
    retail_cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    tables["part"] = pa.table({
        "p_partkey": pa.array(pk, pa.int64()),
        "p_name": _dict_col(rng.integers(0, name_pool, n_part),
                            name_vocab),
        "p_mfgr": _dict_col(brand_m - 1,
                            [f"Manufacturer#{m}" for m in range(1, 6)]),
        "p_brand": _dict_col((brand_m - 1) * 5 + (brand_n - 1),
                             brand_vocab),
        "p_type": _pick_dict(rng, n_part, type_vocab),
        "p_size": pa.array(rng.integers(1, 51, n_part), pa.int32()),
        "p_container": _pick_dict(rng, n_part, cont_vocab),
        "p_retailprice": _decimal_col(retail_cents),
        "p_comment": _words_dict(rng, n_part, 3),
    })

    # supplier ----------------------------------------------------------------
    n_supp = max(1, int(10_000 * sf))
    sk = np.arange(1, n_supp + 1)
    s_nation = rng.integers(0, 25, n_supp)
    # q16: ~5 per 10k suppliers carry 'Customer...Complaints'
    bad = rng.choice(n_supp, size=max(1, n_supp // 2000), replace=False)
    bad_strings = [f"Customer {w} Complaints"
                   for w in _words(rng, len(bad), 2)]
    tables["supplier"] = pa.table({
        "s_suppkey": pa.array(sk, pa.int64()),
        "s_name": _numbered_names("Supplier#", sk),
        "s_address": _words_dict(rng, n_supp, 3),
        "s_nationkey": pa.array(s_nation, pa.int64()),
        "s_phone": pa.array(_phones(rng, s_nation)),
        "s_acctbal": pa.array(_money(rng, n_supp, -999.99, 9999.99)),
        "s_comment": _words_dict(rng, n_supp, 8,
                                 inject=(bad, bad_strings)),
    })

    # partsupp ----------------------------------------------------------------
    ps_part = np.repeat(pk, 4)
    ps_supp = np.empty(len(ps_part), dtype=np.int64)
    for j in range(4):
        # spec: supplier = (partkey + j*(S/4 + (partkey-1)//S)) % S + 1
        ps_supp[j::4] = (pk + j * (n_supp // 4 + (pk - 1) // n_supp)) \
            % n_supp + 1
    tables["partsupp"] = pa.table({
        "ps_partkey": pa.array(ps_part, pa.int64()),
        "ps_suppkey": pa.array(ps_supp, pa.int64()),
        "ps_availqty": pa.array(rng.integers(1, 10_000, len(ps_part)),
                                pa.int32()),
        "ps_supplycost": pa.array(_money(rng, len(ps_part), 1.0, 1000.0)),
        "ps_comment": _words_dict(rng, len(ps_part), 5),
    })

    # customer ----------------------------------------------------------------
    n_cust = max(1, int(150_000 * sf))
    ck = np.arange(1, n_cust + 1)
    c_nation = rng.integers(0, 25, n_cust)
    # q13: some customers' orders carry 'special ... requests' comments —
    # handled on orders below
    tables["customer"] = pa.table({
        "c_custkey": pa.array(ck, pa.int64()),
        "c_name": _numbered_names("Customer#", ck),
        "c_address": _words_dict(rng, n_cust, 3),
        "c_nationkey": pa.array(c_nation, pa.int64()),
        "c_phone": pa.array(_phones(rng, c_nation)),
        "c_acctbal": pa.array(_money(rng, n_cust, -999.99, 9999.99)),
        "c_mktsegment": _pick_dict(rng, n_cust, SEGMENTS),
        "c_comment": _words_dict(rng, n_cust, 6),
    })

    ctx = {"n_part": n_part, "n_supp": n_supp, "n_cust": n_cust,
           "ck": ck, "retail_cents": retail_cents}
    return tables, ctx


def _gen_orders_slice(rng, ok_lo: int, ok_hi: int,
                      ctx: Dict) -> tuple:
    """orders + their lineitems for order keys [ok_lo, ok_hi) — the unit
    of streamed generation (SF100 cannot hold all 600M lineitems as host
    arrays at once)."""
    n_part, n_supp, n_cust = ctx["n_part"], ctx["n_supp"], ctx["n_cust"]
    ck, retail_cents = ctx["ck"], ctx["retail_cents"]
    n_ord = ok_hi - ok_lo
    ok = np.arange(ok_lo, ok_hi)
    # spec: only 2/3 of customers have orders
    cust_with_orders = ck[ck % 3 != 0] if n_cust >= 3 else ck
    o_cust = cust_with_orders[rng.integers(0, len(cust_with_orders), n_ord)]
    o_date = rng.integers(START, END - 150, n_ord)
    special = np.nonzero(rng.random(n_ord) < 0.02)[0]
    special_strings = [f"special {w} requests"
                       for w in _words(rng, min(max(len(special), 1),
                                                512), 2)]
    n_clerks = max(2, n_ord // 1000)
    clerk_vocab = _numbered("Clerk#", np.arange(1, n_clerks))
    orders = pa.table({
        "o_orderkey": pa.array(ok, pa.int64()),
        "o_custkey": pa.array(o_cust, pa.int64()),
        "o_orderstatus": _pick_dict(rng, n_ord, ["O", "F", "P"]),
        "o_totalprice": pa.array(_money(rng, n_ord, 900.0, 450_000.0)),
        "o_orderdate": pa.array(o_date.astype("int32"), pa.int32()).cast(
            pa.date32()),
        "o_orderpriority": _pick_dict(rng, n_ord, PRIORITIES),
        "o_clerk": _dict_col(rng.integers(0, len(clerk_vocab), n_ord),
                             clerk_vocab),
        "o_shippriority": pa.array(np.zeros(n_ord, dtype=np.int32),
                                   pa.int32()),
        "o_comment": _words_dict(rng, n_ord, 5,
                                 inject=(special, special_strings)),
    })

    # lineitem ----------------------------------------------------------------
    lines_per = rng.integers(1, 8, n_ord)
    l_order = np.repeat(ok, lines_per)
    l_odate = np.repeat(o_date, lines_per)
    n_li = len(l_order)
    # per-order line numbers without a Python loop: global position
    # minus the order's starting offset
    starts = np.cumsum(lines_per) - lines_per
    l_line = (np.arange(n_li) - np.repeat(starts, lines_per) + 1) \
        .astype(np.int64)
    l_part = rng.integers(1, n_part + 1, n_li)
    # supplier must be one of the part's 4 partsupp suppliers (q9 join)
    which = rng.integers(0, 4, n_li)
    l_supp = (l_part + which * (n_supp // 4 + (l_part - 1) // n_supp)) \
        % n_supp + 1
    l_qty = rng.integers(1, 51, n_li)
    l_price_cents = l_qty * retail_cents[l_part - 1]
    ship = l_odate + rng.integers(1, 122, n_li)
    commit = l_odate + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    today = (datetime.date(1995, 6, 17) - EPOCH).days
    # returnflag vocab [R, A, N]; linestatus vocab [O, F]
    rf_idx = np.where(receipt <= today, rng.integers(0, 2, n_li), 2)
    ls_idx = np.where(ship > today, 0, 1)
    lineitem = pa.table({
        "l_orderkey": pa.array(l_order, pa.int64()),
        "l_partkey": pa.array(l_part, pa.int64()),
        "l_suppkey": pa.array(l_supp, pa.int64()),
        "l_linenumber": pa.array(l_line, pa.int32()),
        "l_quantity": _decimal_col(l_qty * 100),
        "l_extendedprice": _decimal_col(l_price_cents),
        "l_discount": _decimal_col(rng.integers(0, 11, n_li)),
        "l_tax": _decimal_col(rng.integers(0, 9, n_li)),
        "l_returnflag": _dict_col(rf_idx, ["R", "A", "N"]),
        "l_linestatus": _dict_col(ls_idx, ["O", "F"]),
        "l_shipdate": pa.array(ship.astype("int32"), pa.int32()).cast(
            pa.date32()),
        "l_commitdate": pa.array(commit.astype("int32"), pa.int32()).cast(
            pa.date32()),
        "l_receiptdate": pa.array(receipt.astype("int32"), pa.int32()).cast(
            pa.date32()),
        "l_shipinstruct": _pick_dict(rng, n_li, INSTRUCTIONS),
        "l_shipmode": _pick_dict(rng, n_li, SHIPMODES),
        "l_comment": _words_dict(rng, n_li, 4),
    })
    return orders, lineitem


def _phones(rng, nationkeys: np.ndarray):
    """Spec phone format: 'CC-xxx-xxx-xxxx' with CC = nationkey + 10
    (q22 matches on the country-code prefix)."""
    cc = (nationkeys + 10).astype(str)
    parts = [rng.integers(100, 1000, len(nationkeys)).astype(str),
             rng.integers(100, 1000, len(nationkeys)).astype(str),
             rng.integers(1000, 10_000, len(nationkeys)).astype(str)]
    out = cc
    for p in parts:
        out = np.char.add(np.char.add(out, "-"), p)
    return out


def write_parquet(tables: Dict[str, pa.Table], path: str) -> None:
    import os

    import pyarrow.parquet as pq

    os.makedirs(path, exist_ok=True)
    for name, tbl in tables.items():
        pq.write_table(tbl, os.path.join(path, f"{name}.parquet"))


def write_parquet_streamed(sf: float, path: str, seed: int = 20260729,
                           orders_per_slice: int = 4_000_000) -> None:
    """SF100-capable generation: the six static tables write whole;
    orders/lineitem generate and write in bounded slices of
    ``orders_per_slice`` orders (~4x lineitems), so peak host RAM is one
    slice (~4 GB) instead of the full ~100 GB. orders.parquet /
    lineitem.parquet become multi-file directories (the multi-part
    dataset layout every dbgen -S chunk run produces)."""
    import os

    import pyarrow.parquet as pq

    os.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(seed)
    statics, ctx = _gen_static(sf, rng)
    for name, tbl in statics.items():
        pq.write_table(tbl, os.path.join(path, f"{name}.parquet"))
    statics.clear()
    odir = os.path.join(path, "orders.parquet")
    ldir = os.path.join(path, "lineitem.parquet")
    os.makedirs(odir, exist_ok=True)
    os.makedirs(ldir, exist_ok=True)
    n_ord = max(1, int(1_500_000 * sf))
    lo, i = 1, 0
    while lo <= n_ord:
        hi = min(lo + orders_per_slice, n_ord + 1)
        srng = np.random.default_rng([seed, i])
        orders, lineitem = _gen_orders_slice(srng, lo, hi, ctx)
        pq.write_table(orders, os.path.join(odir, f"part-{i:05d}.parquet"),
                       row_group_size=1 << 20)
        pq.write_table(lineitem,
                       os.path.join(ldir, f"part-{i:05d}.parquet"),
                       row_group_size=1 << 20)
        del orders, lineitem
        lo, i = hi, i + 1


def ensure_dataset(sf: float, base: str = "/tmp",
                   seed: int = 20260729) -> str:
    """Generate-once disk cache (SF100 generation is ~15 min of rng on
    one core; a run must not pay it again). Returns the dataset
    directory; a _DONE marker guards against half-written caches."""
    import os
    import shutil

    tag = f"{sf:g}".replace(".", "p")
    path = os.path.join(base, f"tpch_sf{tag}")
    marker = os.path.join(path, "_DONE")
    if os.path.exists(marker):
        return path
    if os.path.exists(path):
        shutil.rmtree(path)
    if sf <= 10:
        write_parquet(generate_tables(sf, seed), path)
    else:
        write_parquet_streamed(sf, path, seed)
    with open(marker, "w") as f:
        f.write("ok")
    return path


def register_views(spark, tables: Optional[Dict[str, pa.Table]] = None,
                   path: Optional[str] = None) -> None:
    """Register the eight tables as temp views, from memory or a
    write_parquet directory (the latter exercises the scan layer)."""
    names = ["region", "nation", "part", "supplier", "partsupp",
             "customer", "orders", "lineitem"]
    for name in names:
        if path is not None:
            import os

            df = spark.read.parquet(os.path.join(path, f"{name}.parquet"))
        else:
            df = spark.createDataFrame(tables[name])
        df.createOrReplaceTempView(name)
