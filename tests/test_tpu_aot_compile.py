"""What the chip's compiler says, asked without the chip: the Pallas
kernels at the engine's real widths and the flagship fused stage are
compiled for a *described* TPU v5e (``jax.experimental.topologies``),
with ``jax_enable_x64`` on, as the session runs them.

Interpret mode (tests/test_pallas_ops.py) checks results and sees none
of what Mosaic refuses — int64 loop indices and index maps did not lower
under x64 until the kernels typed them int32. Nothing runs here, so
nothing is said about results or times.

This is the only test file that describes the chip. The topology is
described inside a module-scoped fixture and never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file.
"""

import contextlib
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROWS = 4 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _session_settings():
    """x64 on; persistent compile cache off (a compile for a described
    chip is written to it but can never be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    assert jax.config.jax_enable_x64
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture
def as_the_session_runs():
    with _session_settings():
        yield


def _columns(one_chip):
    """(data f32, seg int64 — the engine's group ids under x64, mask)."""
    return (jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((ROWS,), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip))


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("k", [128, 1024])
@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_pallas_kernel_compiles_for_v5e(one_chip, as_the_session_runs,
                                        op, k):
    from spark_tpu.ops import pallas_seg_minmax, pallas_seg_sum

    fns = {
        "sum": lambda d, s, m: pallas_seg_sum(d, s, m, k),
        "count": lambda d, s, m: pallas_seg_sum(
            m.astype(jnp.float32), s, m, k, exact_int=True),
        "min": lambda d, s, m: pallas_seg_minmax(d, s, m, k, is_max=False),
        "max": lambda d, s, m: pallas_seg_minmax(d, s, m, k, is_max=True),
    }
    compiled, text = _compile(fns[op], *_columns(one_chip))
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (k,)
    assert out.dtype == (jnp.int64 if op == "count" else jnp.float32)


def test_flagship_fused_stage_compiles_for_v5e(one_chip,
                                               as_the_session_runs):
    """__graft_entry__.entry(): scan -> filter -> project -> grouped
    aggregate -> sort as ONE program, with int64 / float64 / dictionary
    columns — the XLA path the engine takes for everything else."""
    from __graft_entry__ import entry

    fn, args = entry()
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled, text = _compile(fn, *shapes)
    assert "sort" in text
    assert compiled.memory_analysis().temp_size_in_bytes > 0


Q1_ROWS = 6_001_664  # lineitem at SF1 (6,000,647) in its 1,024-row bucket
Q1_ROWS_SF10 = 59_990_016  # at SF10 (59,989,771): benchmark tpch_sf10_q1


def _stage_at(rows, sliver_sf, one_chip, spark, monkeypatch, run,
              parquet_dir=None):
    """The last fused stage with an aggregate that ``run(spark)`` makes
    the session plan on a sliver of TPC-H, compiled for the described
    chip at ``rows`` rows. With ``parquet_dir`` the tables are scanned
    from parquet written there, as the benchmark's are: the scan decides
    what the device holds."""
    import spark_tpu.compile as compile_pkg
    from spark_tpu.tpch.gen import (generate_tables, register_views,
                                    write_parquet)

    stages = []
    build = compile_pkg.build_stage_callable

    def capture(tier, plan, trace_fn, example_args, *a, **kw):
        stages.append((plan, trace_fn, example_args))
        return build(tier, plan, trace_fn, example_args, *a, **kw)

    monkeypatch.setattr(compile_pkg, "build_stage_callable", capture)
    # an SF no other test uses: the stage is new to the process's cache
    tables = generate_tables(sliver_sf, seed=27)
    if parquet_dir is None:
        register_views(spark, tables)
    else:
        write_parquet(tables, str(parquet_dir))
        register_views(spark, path=str(parquet_dir))
    run(spark)
    _, trace_fn, example_args = [
        s for s in stages if "Aggregate" in s[0].tree_string()][-1]
    cap = max(a.shape[0] for a in jax.tree.leaves(example_args) if a.ndim)

    def at_rows(a):
        shape = ((rows,) + a.shape[1:]
                 if a.ndim and a.shape[0] == cap else a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    compiled, text = _compile(trace_fn, jax.tree.map(at_rows, example_args))
    assert f"[{rows}]" in text
    return compiled, text


def _q1_stage_at(rows, sliver_sf, one_chip, spark, monkeypatch,
                 parquet_dir=None):
    """TPC-H Q1's fused stage (its one execution builds one)."""
    from spark_tpu.tpch.queries import QUERIES

    def run(spark):
        assert len(spark.sql(QUERIES[1]).collect()) == 4

    return _stage_at(rows, sliver_sf, one_chip, spark, monkeypatch, run,
                     parquet_dir)


def test_q1_fused_stage_has_no_group_slot_scatter_on_v5e(
        one_chip, as_the_session_runs, spark, monkeypatch):
    """At SF1's row count the six group slots (3 x 2 dictionary codes)
    are filled by masked reductions. The scatter-adds that took 8.6 s an
    execution on the chip (PERF.md, PR 27) showed in the optimised HLO as
    scatters with an f32[6] result."""
    _, text = _q1_stage_at(Q1_ROWS, 0.0027, one_chip, spark, monkeypatch)
    scatters = [line for line in text.splitlines()
                if "scatter" in line and re.search(r"f(32|64)\[6\]", line)]
    assert not scatters, scatters[:3]


Q15_ROWS, Q15_GROUPS = 2_421_760, 100_000  # tpch_sf10_q15_revenue


def _scatter_combiners(text):
    """The ROOT instruction of every scatter's combiner in an optimised
    program: ``parameter(1)`` where the update is SET, an ``add`` (of an
    f32 pair, for an emulated f64) where it is summed into its slot."""
    roots = []
    for name in re.findall(r" scatter\(.*to_apply=(%[\w.\-]+)", text):
        body = re.search(
            r"^" + re.escape(name) + r" \(.*?\{\n(.*?)\n\}", text,
            re.S | re.M).group(1)
        roots += [line.strip() for line in body.splitlines()
                  if "ROOT" in line]
    return roots


def test_q15_sorted_aggregate_sums_int64_with_no_scatter_add_on_v5e(
        one_chip, as_the_session_runs, spark, monkeypatch, tmp_path):
    """The benchmark's tpch_sf10_q15_revenue at its own shape: the whole
    query's stage over 2,421,760 rows behind the pushed date bounds, the
    sort-based aggregate sized for 100,000 suppliers (100,096 slots). Its
    one decimal sum is a cumsum of the int64 column over the sorted ids,
    so the program holds no float at all — an f64 reaches the optimised
    text as a pair of f32 planes, and the three f64-limb scatter-adds
    over ``f32[100096]`` were 646 of the cell's 1,118 ms an execution
    (PERF.md, PR 36) — and sums nothing into a slot by scatter. The
    scatters that remain SET u32 ranks: the permutation inverses of
    ``seg_bounds``' co-sorted ``searchsorted``, one pair for the sum and
    the COUNT beside it (the compiler merges their searches)."""
    from spark_tpu.physical import kernels as K, operators as P

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "queries")
    with open(os.path.join(bench, "q15_revenue.sql")) as f:
        query = f.read()
    # the sliver has a few hundred suppliers: size the second execution's
    # stage as the cell's count of them does
    recorded = P._AGG_STATS.get
    monkeypatch.setattr(
        P._AGG_STATS, "get",
        lambda key: None if recorded(key) is None else Q15_GROUPS)

    def run(spark):
        first = spark.sql(query).collect()      # counts the groups
        assert spark.sql(query).collect() == first and len(first) == 1

    _, text = _stage_at(Q15_ROWS, 0.0033, one_chip, spark, monkeypatch, run,
                        parquet_dir=tmp_path)
    assert f"[{K.bucket(Q15_GROUPS, 256)}]" in text
    floats = re.findall(r"\bf(?:16|32|64)\[\d*\]", text)
    assert not floats, sorted(set(floats))
    combiners = _scatter_combiners(text)
    print(f"q15 at {Q15_ROWS} rows: {len(combiners)} scatters", combiners)
    assert 1 <= len(combiners) <= 3
    assert all(" parameter(1)" in root for root in combiners), combiners
    sorts = re.findall(r" sort\(", text)
    assert len(sorts) <= 5, len(sorts)   # two lexsorts, one pair of bounds


def _leaf_splits(text, rows):
    """The X64SplitLow/High custom calls over a whole leaf column: what
    the chip runs on every execution to turn an s64 *parameter* into the
    u32 pairs it computes with (1.41 ms each at SF10; PERF.md, PR 29)."""
    return [line for line in text.splitlines()
            if "X64Split" in line and f"u32[{rows}]" in line]


def test_q1_stage_over_a_parquet_scan_splits_no_leaf_column_on_v5e(
        one_chip, as_the_session_runs, spark, monkeypatch, tmp_path):
    """TPC-H's four decimal(12,2) inputs of Q1 fit 32 bits, so a scan
    keeps them on the device as int32 and the stage widens them where
    the convert fuses into its consumers: no split of a leaf column is
    left, and no fusion is added to the 85 the stage had over int64
    parameters (sandbox, PR 30). In-memory tables (createDataFrame) are
    not narrowed and keep their eight splits: the tripwire's control."""
    _, wide = _q1_stage_at(Q1_ROWS, 0.0029, one_chip, spark, monkeypatch)
    assert len(_leaf_splits(wide, Q1_ROWS)) == 8
    _, text = _q1_stage_at(Q1_ROWS, 0.0031, one_chip, spark, monkeypatch,
                           parquet_dir=tmp_path)
    assert not _leaf_splits(text, Q1_ROWS), _leaf_splits(text, Q1_ROWS)[:3]
    fusions = re.findall(r"^\s*%?\S*fusion\S* = ", text, re.M)
    print(f"q1 at {Q1_ROWS} rows over int32 leaves: {len(fusions)} fusions")
    assert len(fusions) <= 85


def _reduce_fusions(text):
    """The optimised program's grouped reductions: each such fusion is
    one pass over its operands (one to an instruction, at its `= `)."""
    return re.findall(r"^\s*%?(\S*reduce\S*fusion\S*) = ", text, re.M)


@pytest.fixture(scope="module")
def q1_stage_at_sf10(one_chip, spark, tmp_path_factory):
    """Compiled once for the tests that read it: the session's cache
    holds the stage after the first plan, so a second capture finds none.
    Scanned from parquet, as the benchmark's tpch_sf10_q1 is."""
    with _session_settings(), pytest.MonkeyPatch.context() as monkeypatch:
        return _q1_stage_at(Q1_ROWS_SF10, 0.0028, one_chip, spark,
                            monkeypatch,
                            parquet_dir=tmp_path_factory.mktemp("sf10"))


def test_q1_fused_stage_fits_one_v5e_at_sf10(q1_stage_at_sf10):
    """The benchmark's tpch_sf10_q1 must run resident: the stage's
    arguments, temporaries and outputs at 59,990,016 rows fit the chip's
    16 GB by the compiler's count (it counts this program, not what else
    the process keeps on the device)."""
    compiled, _ = q1_stage_at_sf10
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes)
    print(f"q1 at {Q1_ROWS_SF10} rows: arguments {m.argument_size_in_bytes}"
          f" temporaries {m.temp_size_in_bytes} outputs "
          f"{m.output_size_in_bytes}")
    # 25 B a row: four decimal columns resident as int32 (their values
    # fit; 41 B a row and 2.46 GB as int64), two int32 codes, the mask
    assert 1.4e9 < m.argument_size_in_bytes < 1.6e9
    assert total < 16e9


def test_q1_fused_stage_reads_each_column_once_at_sf10(q1_stage_at_sf10):
    """Six group slots, five decimal sums and the counts: one pass a
    column and count set, where one reduction a slot made 36 (PR 29; 24
    of the chip's 40 ms an execution were those passes)."""
    _, text = q1_stage_at_sf10
    fusions = _reduce_fusions(text)
    print(f"q1 at {Q1_ROWS_SF10} rows: {len(fusions)} reduce fusions",
          sorted(fusions))
    assert 1 <= len(fusions) <= 8, fusions


def test_int64_seg_sum_with_64_slots_compiles_at_sf10(one_chip,
                                                      as_the_session_runs):
    """The masked rung's largest shape: 64 int64 slots in ONE variadic
    reduction do not compile at this capacity (the compiler materialises
    the operands: 28.66 GB of the chip's 15.75), so the slots go in
    passes of G, each one fusion."""
    from spark_tpu.physical import kernels as K

    shapes = [jax.ShapeDtypeStruct((Q1_ROWS_SF10,), dt, sharding=one_chip)
              for dt in (jnp.int64, jnp.int64, jnp.bool_)]
    compiled, text = _compile(lambda d, s, m: K.seg_sum(d, s, m, 64), *shapes)
    assert len(_reduce_fusions(text)) == K._masked_passes(64, jnp.int64)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes) < 16e9


# q1's result on mesh[4] at SF1 (24 rows a chip), and a bare scan's
MESH_RESULT_CAPACITIES = (96, 4 * 1_500_416)


def _mesh_result_arrays(topo, capacity, floats):
    """(mesh, int arrays, float arrays) of a result sharded over the
    four described chips along the rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_tpu.parallel.mesh import DATA_AXIS

    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    rows = NamedSharding(mesh, P(DATA_AXIS))

    def arrays(shape, *dtypes):
        return tuple(jax.ShapeDtypeStruct(shape, dt, sharding=rows)
                     for dt in dtypes)

    return (mesh,
            arrays((capacity,), jnp.bool_, jnp.int32, jnp.int32, jnp.int64,
                   jnp.bool_, jnp.int64, jnp.bool_),
            arrays((capacity,), *(jnp.float64, jnp.float32)[:floats]))


@pytest.mark.parametrize("floats", [0, 2])
@pytest.mark.parametrize("capacity", MESH_RESULT_CAPACITIES)
def test_mesh_result_packer_keeps_its_planes_sharded_on_v5e(
        topo, as_the_session_runs, capacity, floats):
    """``MeshResult.fetch_host``'s program for the four chips: each packs
    its own shard, so no collective, and the planes stay sharded along
    the rows. An all-integer result's float plane is empty: the chip's
    compiler replicates it whatever ``out_shardings`` says and jax then
    refuses the program (the CPU's does neither; PR 34's second chip
    call died of it)."""
    from spark_tpu.parallel import sharded as S

    mesh, ints, flts = _mesh_result_arrays(topo, capacity, floats)
    sig = (capacity, tuple(("i", str(x.dtype)) for x in ints)
           + tuple(("f", str(x.dtype)) for x in flts))
    compiled = S._mesh_packer(mesh, sig).lower(ints, flts).compile()
    text = compiled.as_text()
    assert not re.search(r"all-gather|all-reduce|all-to-all|collective-permute",
                         text)
    iplane, fplane = compiled.output_shardings
    assert iplane.shard_shape((len(ints), capacity)) == (len(ints),
                                                         capacity // 4)
    if floats:
        assert fplane.shard_shape((floats, capacity)) == (floats,
                                                          capacity // 4)
