"""How a mesh query's result leaves the mesh (PR 34): ``execute_logical``
hands back a ``MeshResult`` whose arrays are still sharded. Its fetch
packs ON the mesh into planes that stay sharded and the planes' copies
to the host are the gather (one program, one wait); a consumer of its
``data`` gets one-device arrays from ``ShardedBatch.to_batch``, one
batched transfer through the host, after which the shards are let go;
what only counts the result (the cache's accounting) gathers nothing.
The rows are the one-chip engine's, order included, whatever the mesh
size and the result's shape, through ``collect()`` and ``toArrow()``;
no sharded array is brought to the host and put back one at a time.
"""

import datetime
import decimal

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_tpu import metrics
from spark_tpu.api.row import Row
from spark_tpu.api.session import SparkSession
from spark_tpu.columnar.batch import _PACKER_CACHE, Batch
from spark_tpu.parallel import sharded as S
from spark_tpu.parallel.mesh import make_mesh
from spark_tpu.plan.optimizer import optimize

MESHES = (1, 2, 4, 8)
ROWS = 3000     # 384 a shard on eight devices: live rows in every shard
RAGGED = 1000   # 125 a shard on eight devices, padded to 128
DEC = pa.decimal128(12, 2)
DAY0 = datetime.date(1995, 1, 1)

#: result shape -> SQL. Exact types wherever rows are combined (a float
#: sum's bits follow the order of combination, which the mesh changes);
#: no ORDER BY where flat order = the scan's row order is what is held.
SHAPES = {
    # grouped, sorted, nullable decimal sums, two dictionary-coded strings
    "q1": """select flag, status, sum(qty) as sum_qty, sum(price) as sum_base,
                    sum(price * (1 - disc)) as sum_disc_price,
                    sum(big) as sum_big, count(*) as n
             from fact where ship <= date '1995-10-28'
             group by flag, status order by flag, status""",
    "global": """select sum(price) as revenue, count(big) as n,
                        min(ship) as first from fact""",
    "empty": "select flag, price, f from fact where qty < 0",
    "float": "select okey, f, f * 2 as f2 from fact where okey % 3 = 0",
    # a 2-D column: the mesh projects one (it cannot exchange one)
    "array": "select okey, array(okey, big) as ab from fact where okey % 3 = 1",
    "scan": "select * from fact",
    "ragged": "select k, v, tag from small where k % 7 <> 0",
}


def _tables():
    rng = np.random.default_rng(34)

    def dec(hi, nulls=0.0):
        v = rng.integers(0, hi, ROWS)
        gone = rng.random(ROWS) < nulls
        return pa.array([None if g else decimal.Decimal(int(x)).scaleb(-2)
                         for x, g in zip(v, gone)], DEC)

    f = rng.normal(size=ROWS) * 100
    fact = pa.table({
        "okey": pa.array(np.arange(ROWS), pa.int64()),
        "flag": pa.array(rng.choice(["A", "N", "R"], ROWS)),
        "status": pa.array(rng.choice(["F", "O"], ROWS)),
        "qty": dec(5001), "price": dec(10_494_951, nulls=0.2),
        "disc": dec(11),
        "big": pa.array(rng.integers(-(1 << 40), 1 << 40, ROWS), pa.int64(),
                        mask=rng.random(ROWS) < 0.2),
        "f": pa.array(f, pa.float64(), mask=rng.random(ROWS) < 0.1),
        "ship": pa.array([DAY0 + datetime.timedelta(days=int(d))
                          for d in rng.integers(0, 400, ROWS)], pa.date32()),
    })
    small = pa.table({
        "k": pa.array(np.arange(RAGGED), pa.int64()),
        "v": pa.array(rng.normal(size=RAGGED), pa.float64()),
        "tag": pa.array(rng.choice(["red", "green", "blue"], RAGGED)),
    })
    return {"fact": fact, "small": small}


TABLES = _tables()


def _register(session):
    for name, table in TABLES.items():
        session.createDataFrame(table).createOrReplaceTempView(name)
    return session


@pytest.fixture(scope="module")
def want(spark):
    """shape -> (rows, arrow table) by the one-chip engine."""
    _register(spark)
    out = {}
    for shape, text in SHAPES.items():
        df = spark.sql(text)
        out[shape] = ([r.asDict() for r in df.collect()], df.toArrow())
    return out


@pytest.fixture
def on_mesh(spark):
    """mesh size -> a ``mesh[d]`` session with the tables registered;
    the suite's own session is left as it was found."""
    prev = SparkSession._active

    def session(d):
        SparkSession._reset()
        return _register(
            SparkSession.builder.master(f"mesh[{d}]").getOrCreate())

    yield session
    SparkSession._reset()
    SparkSession._active = prev


def _gather_spans(since):
    return [e for e in metrics.recent(4096)
            if e["kind"] == "span" and e.get("op") == "gather"
            and e["n"] > since]


def _builds(since):
    return [e for e in metrics.recent(4096)
            if e["kind"] == "gather" and e["n"] > since]


def _last_n():
    last = metrics.recent(1)
    return last[-1]["n"] if last else -1


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("d", MESHES)
def test_rows_equal_the_one_chip_engines(want, on_mesh, d, shape):
    rows, table = want[shape]
    assert (shape == "empty") == (not rows)
    df = on_mesh(d).sql(SHAPES[shape])
    since = _last_n()
    assert [r.asDict() for r in df.collect()] == rows
    got = df.toArrow()
    assert got.schema.types == table.schema.types
    assert got.to_pylist() == table.to_pylist()
    spans = _gather_spans(since)
    assert [e["path"] for e in spans] == ["planes", "planes"]
    assert all(e["name"] == "fetch.copy" for e in spans)


def test_bare_scan_and_ragged_results_live_where_they_are_meant_to():
    """The shapes above hold what they are named for on eight devices:
    the bare scan's live rows sit in every shard, and the ragged table's
    125 rows a shard are padded to 128."""
    from spark_tpu.columnar.arrow import from_arrow

    mesh = make_mesh(8)
    sb = S.ShardedBatch.from_batch(from_arrow(TABLES["fact"]), mesh)
    live = np.asarray(sb.data.row_mask).reshape(8, -1).sum(axis=1)
    assert live.min() > 0 and live.sum() == ROWS
    sb = S.ShardedBatch.from_batch(from_arrow(TABLES["small"]), mesh)
    assert -(-RAGGED // 8) % 128 and sb.per_device_capacity == 128


@pytest.fixture
def sharded_conversions(monkeypatch):
    """``np.asarray`` calls on an array that spans several devices made
    on the way from the last stage to the host or to one device (the
    mesh's stages may read a mask back; what is held is
    ``MeshResult._fetch_host`` and ``ShardedBatch.to_batch``). The CPU
    backend does not honour ``jax.transfer_guard``, so count."""
    from jax._src import array as jax_array

    seen = {"inside": 0, "converted": 0}
    to_array = jax_array.ArrayImpl.__array__

    def counting_array(self, *args, **kwargs):
        if seen["inside"] and len(self.sharding.device_set) > 1:
            seen["converted"] += 1
        return to_array(self, *args, **kwargs)

    def counted(fn):
        def inside(self, *args, **kwargs):
            seen["inside"] += 1
            try:
                return fn(self, *args, **kwargs)
            finally:
                seen["inside"] -= 1
        return inside

    monkeypatch.setattr(jax_array.ArrayImpl, "__array__", counting_array)
    monkeypatch.setattr(S.ShardedBatch, "to_batch",
                        counted(S.ShardedBatch.to_batch))
    monkeypatch.setattr(S.MeshResult, "_fetch_host",
                        counted(S.MeshResult._fetch_host))
    return seen


def test_q1_leaves_the_mesh_in_one_program_built_once(want, on_mesh,
                                                      sharded_conversions):
    rows, _ = want["q1"]
    _PACKER_CACHE.clear()       # the cases above built this signature
    session = on_mesh(4)
    since = _last_n()
    for _ in range(3):
        assert [r.asDict()
                for r in session.sql(SHAPES["q1"]).collect()] == rows
    spans = _gather_spans(since)
    assert [e["path"] for e in spans] == ["planes"] * 3
    # seven columns, four of them nullable, and the row mask
    assert {e["arrays"] for e in spans} == {12}
    assert len({e["bytes"] for e in spans}) == 1 and spans[0]["bytes"] > 0
    (built,) = _builds(since)
    assert (built["mesh"], built["arrays"]) == (4, 12)
    ((mesh, _sig),) = _PACKER_CACHE     # one packer, and it is the mesh's
    assert mesh is session._mesh
    # an all-integer result is ONE plane: one assembly an execution,
    # where the per-array hop made twelve
    assert sharded_conversions["converted"] == 3


def test_the_tripwire_sees_a_per_array_hop(sharded_conversions):
    """What the count above would read had the hop been kept."""
    from spark_tpu.columnar.arrow import from_arrow

    sb = S.ShardedBatch.from_batch(from_arrow(TABLES["small"]), make_mesh(4))
    sharded_conversions["inside"] += 1
    np.asarray(sb.data.row_mask)
    assert sharded_conversions["converted"] == 1


@pytest.mark.parametrize("shape", ["q1", "float", "array", "empty"])
@pytest.mark.parametrize("d", [1, 4])
def test_a_consumer_of_the_arrays_gets_them_on_one_device(
        want, on_mesh, sharded_conversions, d, shape):
    """``execute_logical``'s contract: the result is a ``Batch``. What
    counts it leaves it sharded; ``data`` gathers once, in one batched
    transfer (path ``host``: one assembly an array, no program), to
    arrays on the mesh's first device, and lets the shards go: the
    result is then a one-device batch, its fetch included."""
    rows, _ = want[shape]
    session = on_mesh(d)
    df = session.sql(SHAPES[shape])
    since = _last_n()
    result = df._execute()
    assert isinstance(result, S.MeshResult) and isinstance(result, Batch)
    sharded = jax.tree_util.tree_leaves(result._held[0])
    nbytes = sum(x.nbytes for x in sharded)
    assert result.capacity == sharded[-1].shape[0]
    assert result.num_valid_rows() == len(rows)
    assert result.device_nbytes() == nbytes and result.narrowed() == 0
    assert not _gather_spans(since) and result._held[1] is session._mesh
    _PACKER_CACHE.clear()
    data = result.data
    assert result.data is data and result._held == (data, None)
    (span,) = _gather_spans(since)
    assert (span["path"], span["arrays"], span["bytes"]) == (
        "host", len(sharded), nbytes)
    assert not _builds(since) and not _PACKER_CACHE
    assert sharded_conversions["converted"] == (len(sharded) if d > 1
                                                else 0)
    first = session._mesh.devices.flat[0]
    for got, was in zip(jax.tree_util.tree_leaves(data), sharded):
        assert got.devices() == {first}
        assert got.dtype == was.dtype and got.shape == was.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(was))
    assert result.device_nbytes() == nbytes
    one = Batch(result.schema, data)
    assert [Row.from_dict(r).asDict() for r in one.to_pylist()] == rows
    assert result.to_pylist() == one.to_pylist()
    # the gathered result fetches as any one-device batch does
    assert len(_gather_spans(since)) == 1 and not _builds(since)
    assert all(not isinstance(key[0], jax.sharding.Mesh)
               for key in _PACKER_CACHE)


@pytest.mark.parametrize("shape", ["q1", "float", "scan", "empty"])
@pytest.mark.parametrize("d", [4, 8])
def test_caching_a_mesh_result_gathers_nothing_until_it_is_read(
        want, on_mesh, d, shape):
    """``store.put`` only counts the result: no gather span, no program
    built, and the bytes it books are the shards'. The first query over
    the cached plan reads the arrays: one gather, the shards go, and
    the store holds what it booked, on one device."""
    rows, _ = want[shape]
    session = on_mesh(d)
    store = session.memory_store
    df = session.sql(SHAPES[shape])
    result = df._execute()
    nbytes = sum(x.nbytes
                 for x in jax.tree_util.tree_leaves(result._held[0]))
    _PACKER_CACHE.clear()
    since = _last_n()
    assert store.put(("test_mesh_gather", d, shape), result)
    assert store.bytes_used() == nbytes
    assert not _gather_spans(since) and not _builds(since)
    assert not _PACKER_CACHE and result._held[1] is session._mesh
    store.remove(("test_mesh_gather", d, shape))

    df.cache()
    since = _last_n()
    assert [r.asDict() for r in df.collect()] == rows
    ((held, booked),) = [(e.batch, e.nbytes)
                         for e in store._entries.values()]
    assert isinstance(held, S.MeshResult) and held._held[1] is None
    first = session._mesh.devices.flat[0]
    leaves = jax.tree_util.tree_leaves(held._held[0])
    assert all(x.devices() == {first} for x in leaves)
    assert booked == store.bytes_used() == sum(x.nbytes for x in leaves)
    assert [e["path"] for e in _gather_spans(since)] == ["host", "planes"]
    since = _last_n()
    assert [r.asDict() for r in df.collect()] == rows
    assert [e["path"] for e in _gather_spans(since)] == ["planes"]
    df.unpersist()
    assert store.bytes_used() == 0


def test_a_cached_mesh_result_is_read_again(want, on_mesh):
    """The cache manager keeps the result's arrays and a later query
    scans them: the gathered copy goes back over the mesh."""
    rows, _ = want["q1"]
    df = on_mesh(4).sql(SHAPES["q1"]).cache()
    assert df.count() == len(rows)
    assert [r.asDict() for r in df.collect()] == rows
    assert [r.asDict() for r in df.where("n > 0").collect()] == rows


@pytest.mark.parametrize("d", MESHES)
def test_to_batch_is_one_batched_transfer_at_any_size(
        on_mesh, sharded_conversions, d):
    """No size line: the bare scan's 3,000 rows and the ragged table's
    padded shards leave by the same one transfer as Q1's four rows, and
    the assemblies are one an array, of copies already on their way."""
    session = on_mesh(d)
    for shape in ("scan", "ragged", "q1"):
        ex = session.mesh_executor
        sb = ex.run(ex.plan(optimize(session.sql(SHAPES[shape])._plan)))
        leaves = jax.tree_util.tree_leaves(sb.data)
        before = sharded_conversions["converted"]
        since = _last_n()
        one = sb.to_batch()
        (span,) = _gather_spans(since)
        assert (span["path"], span["arrays"]) == ("host", len(leaves))
        assert sharded_conversions["converted"] - before == (
            len(leaves) if d > 1 else 0)
        for got, was in zip(jax.tree_util.tree_leaves(one.data), leaves):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(was))
