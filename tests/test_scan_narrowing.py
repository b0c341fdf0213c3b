"""What a resident scan leaves on the device (PR 31): a 1-D int64-backed
column (decimal, bigint) whose observed values fit 32 bits is held as
int32 and every stage widens it at trace entry, so the rows are the ones
the un-narrowed run gives — on both engines, for Q1-, Q6- and Q14-shaped
queries, whatever the columns hold. ``createDataFrame`` is not narrowed
and is the un-narrowed run; pandas over exact integers is the reference.
"""

import datetime
import decimal
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_tpu import metrics

ROWS, PARTS = 3000, 64
DEC = pa.decimal128(12, 2)
DAY0 = datetime.date(1995, 1, 1)

#: how the decimal and bigint columns are filled; what decides is what
#: the scan observes, so every kind runs the same queries
KINDS = ("fits", "wide", "negative", "nullable", "empty")

Q1 = """
select flag, status, sum(qty) as sum_qty, sum(price) as sum_base,
       sum(price * (1 - disc)) as sum_disc_price,
       sum(price * (1 - disc) * (1 + tax)) as sum_charge,
       sum(big) as sum_big, count(*) as n
from fact where ship <= date '{cutoff}'
group by flag, status order by flag, status"""
Q6 = """
select sum(price * disc) as revenue, count(big) as n from fact
where ship <= date '{cutoff}' and disc between 0.02 and 0.07 and qty < 40"""
Q14 = """
select sum(case when ptype like 'PROMO%' then price * (1 - disc)
                else 0 end) as promo,
       sum(price * (1 - disc)) as total, count(*) as n
from fact, dim where pkey = okey and ship <= date '{cutoff}'"""
QUERIES = {"q1": Q1, "q6": Q6, "q14": Q14}
#: int64-backed columns each query's scans hold (q6's qty is only in its
#: pushed filter): all of them (they narrow when they fit), and those
#: among them that 'wide' keeps inside int32 (qty, disc, tax), so that
#: one table holds both kinds of column
NARROWED = {"q1": (5, 3), "q6": (3, 1), "q14": (4, 1)}


def _unscaled(kind, rng, n, hi):
    """Unscaled decimal / bigint values and their validity."""
    valid = None
    if kind == "wide":          # outside int32: stays int64 on the device
        v = rng.integers(1 << 33, 1 << 36, n)
    elif kind == "negative":    # inside, down to int32's own minimum
        v = rng.integers(-hi, hi, n)
        v[0], v[1] = -(1 << 31), (1 << 31) - 1
    else:
        v = rng.integers(0, hi, n)
    if kind == "nullable":
        valid = rng.random(n) > 0.2
    return v.astype(np.int64), valid


def _tables(kind):
    rng = np.random.default_rng(31)

    def nullable_ints(v, valid):
        return pd.arrays.IntegerArray(
            v, np.zeros(len(v), bool) if valid is None else ~valid)

    cols, frame = {}, {}
    v, valid = _unscaled(kind, rng, ROWS, 10_494_951)
    frame["price"] = nullable_ints(v, valid)
    cols["price"] = pa.array(
        [None if x is pd.NA else decimal.Decimal(int(x)).scaleb(-2)
         for x in frame["price"]], DEC)
    # the quantity and the rates are TPC-H's in every kind: Q6's filter
    # must pass rows and the arithmetic must not overflow
    for name, hi in (("qty", 5001), ("disc", 11), ("tax", 9)):
        v = rng.integers(0, hi, ROWS).astype(np.int64)
        cols[name], frame[name] = pa.array(
            [decimal.Decimal(int(x)).scaleb(-2) for x in v], DEC), v
    big, bvalid = _unscaled(kind, rng, ROWS, 1 << 31)
    cols["big"] = pa.array(big, pa.int64(),
                           mask=None if bvalid is None else ~bvalid)
    frame["big"] = nullable_ints(big, bvalid)
    # the join key: a wide kind's keys pass 32 bits on BOTH sides
    base = (1 << 33) if kind == "wide" else (
        -(PARTS // 2) if kind == "negative" else 0)
    okey = base + rng.integers(0, PARTS, ROWS)
    cols["okey"], frame["okey"] = pa.array(okey, pa.int64()), okey
    flag = rng.choice(["A", "N", "R"], ROWS)
    status = rng.choice(["F", "O"], ROWS)
    days = rng.integers(0, 400, ROWS)
    cols["flag"], cols["status"] = pa.array(flag), pa.array(status)
    cols["ship"] = pa.array([DAY0 + datetime.timedelta(days=int(d))
                             for d in days], pa.date32())
    frame.update(flag=flag, status=status, days=days)
    ptype = rng.choice(["PROMO BRUSHED", "STANDARD TIN", "PROMO ANODIZED"],
                       PARTS)
    dim = pa.table({"pkey": pa.array(base + np.arange(PARTS), pa.int64()),
                    "ptype": pa.array(ptype)})
    return pa.table(cols), dim, pd.DataFrame(frame), dict(
        zip((base + np.arange(PARTS)).tolist(), ptype))


def _dec(x, scale):
    return None if x is None else decimal.Decimal(int(x)).scaleb(-scale)


def _nsum(s):
    """SQL's sum: NULLs skipped, NULL when nothing is left (exact)."""
    s = s.dropna()
    return None if not len(s) else sum(int(x) for x in s)


def _reference(name, f, ptypes, cutoff_days):
    """The rows by pandas over exact Python integers."""
    f = f[f.days <= cutoff_days]
    disc_price = f.price * (100 - f.disc)                    # scale 4
    if name == "q1":
        charge = disc_price * (100 + f.tax)                  # scale 6
        rows = []
        for (flag, status), g in f.groupby(["flag", "status"]):
            i = g.index
            rows.append((flag, status, _dec(_nsum(g.qty), 2),
                         _dec(_nsum(g.price), 2),
                         _dec(_nsum(disc_price[i]), 4),
                         _dec(_nsum(charge[i]), 6), _nsum(g.big), len(g)))
        return rows
    if name == "q6":
        keep = (f.disc >= 2) & (f.disc <= 7) & (f.qty < 4000)
        g = f[keep]
        return [(_dec(_nsum(g.price * g.disc), 4),
                 int(g.big.notna().sum()))]
    promo = f.okey.map(ptypes).str.startswith("PROMO")
    return [(_dec(_nsum(disc_price[promo]), 4),
             _dec(_nsum(disc_price), 4), len(f))]


def _run(spark, text):
    return [tuple(r.asDict().values()) for r in spark.sql(text).collect()]


def _scan_events(since):
    return [e for e in metrics.recent(4096)
            if e["kind"] == "scan" and e["n"] > since]


def _last_n():
    last = metrics.recent(1)
    return last[-1]["n"] if last else -1


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """kind -> (parquet directory, fact, dim, pandas frame, part types)."""
    out = {}
    for kind in KINDS:
        fact, dim, frame, ptypes = _tables(kind)
        d = str(tmp_path_factory.mktemp(kind))
        pq.write_table(fact, os.path.join(d, "fact.parquet"))
        pq.write_table(dim, os.path.join(d, "dim.parquet"))
        out[kind] = (d, fact, dim, frame, ptypes)
    return out


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("query", list(QUERIES))
def test_rows_equal_the_unnarrowed_runs_and_pandas(engine, written, query,
                                                   kind):
    d, fact, dim, frame, ptypes = written[kind]
    # 'empty': the pushed date filter leaves the scan no row to observe
    cutoff_days = -1 if kind == "empty" else 300
    text = QUERIES[query].format(
        cutoff=DAY0 + datetime.timedelta(days=cutoff_days))
    want = _reference(query, frame, ptypes, cutoff_days)

    engine.createDataFrame(fact).createOrReplaceTempView("fact")
    engine.createDataFrame(dim).createOrReplaceTempView("dim")
    wide = _run(engine, text)
    assert wide == want

    since = _last_n()
    for name in ("fact", "dim"):
        engine.read.parquet(
            os.path.join(d, f"{name}.parquet")).createOrReplaceTempView(name)
    # the blocking first run, the traced replay, the steady state
    for _ in range(3):
        assert _run(engine, text) == wide
    narrowed = sum(e["narrowed"] for e in _scan_events(since))
    fits, rates_only = NARROWED[query]
    if kind == "empty":     # nothing observed; q14's dim is not filtered
        assert narrowed == (1 if query == "q14" else 0)
    else:
        assert narrowed == (rates_only if kind == "wide" else fits)


def _int64_backed(table):
    return [n for n, t in zip(table.column_names, table.schema.types)
            if pa.types.is_int64(t) or pa.types.is_decimal(t)]


@pytest.mark.parametrize("kind", ["fits", "negative", "nullable"])
def test_a_bare_scan_of_a_narrowed_table_gives_the_files_rows(engine,
                                                              written, kind):
    """No stage runs, so nothing widens: the fetch and the arrow export
    give the schema's types and the file's values all the same."""
    d, fact, *_ = written[kind]
    since = _last_n()
    df = engine.read.parquet(os.path.join(d, "fact.parquet"))
    got = df.toArrow()
    (event,) = _scan_events(since)
    assert event["narrowed"] == len(_int64_backed(fact))
    assert event["resident_bytes"] < ROWS * 8 * len(_int64_backed(fact))
    assert got.schema.types == fact.schema.types
    assert got.to_pylist() == fact.to_pylist()
    assert [r.asDict() for r in df.collect()] == fact.to_pylist()


def test_two_tables_of_one_schema_one_narrowed_share_no_program(spark,
                                                                written):
    """Same schema, same capacity, one after the other through one
    session: the stage cache's key carries the leaves' device dtypes,
    so the program that widens int32 is never handed int64 arrays."""
    from spark_tpu.physical import planner as PL

    text = Q1.format(cutoff=DAY0 + datetime.timedelta(days=300))
    PL._STAGE_CACHE.clear()  # the cases above ran these programs already
    keys, rows = {}, {}
    for _ in range(2):
        for kind in ("fits", "wide"):
            d, _, _, frame, ptypes = written[kind]
            spark.read.parquet(
                os.path.join(d, "fact.parquet")).createOrReplaceTempView(
                "fact")
            before = set(PL._STAGE_CACHE.keys())
            for _ in range(2):
                rows[kind] = _run(spark, text)
                assert rows[kind] == _reference("q1", frame, ptypes, 300)
            keys.setdefault(kind, set()).update(
                set(PL._STAGE_CACHE.keys()) - before)
    assert keys["fits"] and keys["wide"]
    assert not keys["fits"] & keys["wide"]
    assert rows["fits"] != rows["wide"]


@pytest.mark.compile
def test_the_executable_store_keeps_a_narrowed_scans_program_apart(
        written, tmp_path):
    """An AOT executable is specialised to its arguments' dtypes: with
    the store on, the un-narrowed table must not be served the narrowed
    table's executable (nor the other way round)."""
    from test_compile import _forget_process_state, _session

    text = "select k, sum(v) as s, count(*) as c from t group by k order by k"
    rng = np.random.default_rng(31)
    tables = {}
    for kind, lo in (("fits", 0), ("wide", 1 << 40)):
        t = pa.table({"k": pa.array(rng.integers(0, 8, 4000), pa.int64()),
                      "v": pa.array(lo + rng.integers(0, 1000, 4000),
                                    pa.int64())})
        pq.write_table(t, str(tmp_path / f"{kind}.parquet"))
        f = t.to_pandas().groupby("k").v.agg(["sum", "count"])
        tables[kind] = [(int(k), int(r["sum"]), int(r["count"]))
                        for k, r in f.iterrows()]
    conf = {"spark.tpu.compile.store.dir": str(tmp_path / "store")}
    for _ in range(2):  # the second session loads what the first kept
        _forget_process_state()
        with _session(**conf) as s:
            for kind in ("fits", "wide", "fits"):
                s.read.parquet(str(
                    tmp_path / f"{kind}.parquet")).createOrReplaceTempView("t")
                for _ in range(3):
                    assert _run(s, text) == tables[kind]


@pytest.mark.parametrize("kind,wide_args", [("fits", 0), ("wide", 2)])
def test_stablehlo_leaf_tripwire(spark, written, monkeypatch, kind,
                                 wide_args):
    """Q1's lowered stage (``jit_stage_fn``) takes no i64 tensor of the
    table's capacity when its columns fit: the chip splits every s64
    *parameter* into u32 pairs on every execution (1.4 ms a column at
    SF10; PERF.md, PR 29), and an s32 one not at all. A table whose
    price and big pass 32 bits keeps those two as i64: the control."""
    import re

    import jax

    import spark_tpu.compile as compile_pkg
    from spark_tpu.physical import planner as PL

    stages = []
    build = compile_pkg.build_stage_callable

    def capture(tier, plan, trace_fn, example_args, *a, **kw):
        stages.append((plan, trace_fn, example_args))
        return build(tier, plan, trace_fn, example_args, *a, **kw)

    monkeypatch.setattr(compile_pkg, "build_stage_callable", capture)
    PL._STAGE_CACHE.clear()  # so that the stage is built, and caught, here
    d, _, _, frame, ptypes = written[kind]
    spark.read.parquet(
        os.path.join(d, "fact.parquet")).createOrReplaceTempView("fact")
    text = Q1.format(cutoff=DAY0 + datetime.timedelta(days=300))
    assert _run(spark, text) == _reference("q1", frame, ptypes, 300)
    (stage,) = [s for s in stages if "Aggregate" in s[0].tree_string()]
    _, trace_fn, example_args = stage
    lowered = jax.jit(trace_fn).lower(example_args).as_text()
    assert "module @jit_stage_fn" in lowered
    (signature,) = re.findall(r"func\.func public @main\((.*?)\) ->",
                              lowered, re.S)
    cap = example_args[0].capacity
    assert len(re.findall(rf"tensor<{cap}xi64>", signature)) == wide_args
    assert len(re.findall(rf"tensor<{cap}xi32>", signature)) == 7 - wide_args
