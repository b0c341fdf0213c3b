"""What PR 28 added to the tracing: ``jax.named_scope("spark.<Operator>")``
round each operator's ``trace()`` of a fused stage (both engines), and the
build events ``join`` and ``sort`` beside ``seg_sum``. A scope is a name in
an operation's ``op_name``; a build event is recorded when a program piece
is BUILT and never when a compiled stage runs. Neither touches data."""

import ast
import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from spark_tpu import metrics, trace
from spark_tpu.physical import kernels as K

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _events(kind, fn):
    last = metrics.recent(1)
    seen = last[-1]["n"] if last else -1
    out = fn()
    return out, [e for e in metrics.recent(4096)
                 if e["n"] > seen and e["kind"] == kind]


@contextlib.contextmanager
def _captured_stages():
    """The stages the session builds inside the block, as
    (plan, trace function, example arguments)."""
    import spark_tpu.compile as compile_pkg

    captured = []
    build = compile_pkg.build_stage_callable

    def capture(tier, plan, trace_fn, example_args, *a, **kw):
        captured.append((plan, trace_fn, example_args))
        return build(tier, plan, trace_fn, example_args, *a, **kw)

    compile_pkg.build_stage_callable = capture
    try:
        yield captured
    finally:
        compile_pkg.build_stage_callable = build


@pytest.fixture(scope="module")
def stages(spark):
    """The fused stages of TPC-H Q1 (one execution) and Q14 (three: the
    join blocks the first time and is traced the second), their rows, and
    the ``join`` events of each Q14 execution."""
    from spark_tpu.tpch.gen import generate_tables, register_views
    from spark_tpu.tpch.queries import QUERIES

    with _captured_stages() as captured:
        # an SF no other test uses: the stages are new to the process
        register_views(spark, generate_tables(0.0031, seed=28))
        rows = {1: [spark.sql(QUERIES[1]).collect()], 14: []}
        joins = []
        for _ in range(3):
            got, events = _events(
                "join", lambda: spark.sql(QUERIES[14]).collect())
            rows[14].append(got)
            joins.append(events)
    return captured, rows, joins


def _scopes(trace_fn, example_args):
    text = jax.jit(trace_fn).lower(example_args).as_text(debug_info=True)
    return set(re.findall(r"spark\.(\w+Exec)", text))


@pytest.mark.parametrize("query, operators", [
    ("q1", {"HashAggregateExec", "FilterExec", "SortExec"}),
    ("q14", {"HashAggregateExec", "JoinExec"}),
])
def test_lowered_stage_names_its_operators(stages, query, operators):
    captured = stages[0]
    wanted = "Join" if query == "q14" else "Sort"
    found = [s for s in captured if wanted in s[0].tree_string()
             and "Aggregate" in s[0].tree_string()]
    assert found, [s[0].tree_string() for s in captured]
    _, trace_fn, example_args = found[-1]
    assert operators <= _scopes(trace_fn, example_args)


@pytest.fixture(scope="module")
def mesh_spark(spark):
    """A session of its own over mesh[2]. ``builder.master("mesh[2]")
    .getOrCreate()`` would hand back the active one-chip session with a
    conf key set: a session builds its mesh when it is made."""
    from spark_tpu import conf as CF
    from spark_tpu.api.session import SparkSession

    session = SparkSession("scopes-mesh", {CF.MESH_DEVICES.key: 2})
    assert session.mesh_executor is not None
    return session


def test_mesh_stage_names_its_operators(mesh_spark):
    """The mesh's counterpart (parallel/executor.py::_run_stage_inner):
    the shard_map'd local function carries the same scopes."""
    with _captured_stages() as captured:
        df = mesh_spark.range(4099).filter("id % 7 = 3").groupBy().count()
        assert df.collect()[0][0] == len(range(3, 4099, 7))
    assert captured
    names = set()
    for _plan, trace_fn, example_args in captured:
        names |= _scopes(trace_fn, example_args)
    assert "FilterExec" in names and any("Agg" in n for n in names)


@pytest.mark.parametrize("engine", ["one_chip", "mesh"])
def test_second_execution_builds_nothing(spark, mesh_spark, engine):
    """The shared builder (physical/stage.py::build_stage) runs on a
    stage-cache miss only: an execution that finds its stages cached
    records no build event, compiles nothing and leaves both caches the
    size they were."""
    from spark_tpu.parallel import executor as MX
    from spark_tpu.physical import planner as PL

    session = spark if engine == "one_chip" else mesh_spark

    def run():
        # 4,111 rows: a shape no other test of this file compiles
        return session.range(4111).filter("id % 5 = 2").groupBy().count(
        ).collect()[0][0]

    def sizes():
        return len(PL._STAGE_CACHE), len(MX._DIST_STAGE_CACHE)

    with _captured_stages() as first:
        want = run()
        run()   # one chip: a first run records its output capacity
    before = sizes()
    last = metrics.recent(1)[-1]["n"]
    with _captured_stages() as again:
        assert run() == want == len(range(2, 4111, 5))
    assert first and again == [] and sizes() == before
    assert [e["kind"] for e in metrics.recent(4096) if e["n"] > last
            and e["kind"] in trace.BUILD_EVENTS | {"stage_compile"}] == []
    # each engine built its own function, under its own name
    assert {fn.__name__ for _plan, fn, _args in first} == {
        "stage_fn" if engine == "one_chip" else "local_fn"}


def test_results_are_byte_identical_under_a_profiler_session(
        spark, stages, tmp_path):
    """Scopes and span annotations are names: the rows of Q1 and Q14 are
    the same objects' worth of bytes with a profiler collecting."""
    from spark_tpu.tpch.queries import QUERIES

    rows = stages[1]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        traced = {n: spark.sql(QUERIES[n]).collect() for n in (1, 14)}
    finally:
        jax.profiler.stop_trace()
    for n in (1, 14):
        # against the same compiled stage without a session (Q14's eager
        # blocking run rounds its one float division an ulp apart)
        plain = rows[n][-1]
        assert [tuple(r.asDict().items()) for r in traced[n]] == [
            tuple(r.asDict().items()) for r in plain]
        assert repr(traced[n]) == repr(plain)


def test_sort_events_are_recorded_when_a_sort_is_built():
    mask = jnp.arange(5000) % 3 == 0
    _, eager = _events("sort", lambda: K.compaction_permutation(mask))
    assert [(e["site"], e["rows"], e["dtype"]) for e in eager] == [
        ("compaction", 5000, "bool")]
    jitted = jax.jit(K.compaction_permutation)
    _, traced = _events("sort", lambda: jitted(mask))
    _, again = _events("sort", lambda: jitted(mask))
    assert len(traced) == 1 and again == []
    keys = [K.SortKey(jnp.arange(5000, dtype=jnp.int64), mask),
            K.SortKey(jnp.arange(5000, dtype=jnp.int32), None)]
    _, lex = _events("sort", lambda: K.lexsort_permutation(keys, mask))
    # one a key, one for the nullable key's validity, one for the live rows
    assert [(e["site"], e["dtype"]) for e in lex] == [
        ("lexsort", "int32"), ("lexsort", "int64"), ("lexsort", "bool"),
        ("lexsort", "bool")]
    a = jnp.sort(jnp.arange(300_000, dtype=jnp.int64))
    _, few = _events("sort", lambda: K.searchsorted(a, a[:100]))
    _, many = _events("sort", lambda: K.searchsorted(a, a[:50_000]))
    assert few == []                                  # binary search
    assert [(e["site"], e["rows"]) for e in many] == [
        ("searchsorted", 350_000)]                    # co-sort
    _, index = _events("sort", lambda: K.make_join_index(
        a[:5000], mask, None))
    assert [(e["site"], e["rows"]) for e in index] == [("join_index", 5000)]


def test_join_event_names_the_rung_of_the_traced_join(stages):
    """Q14's first execution blocks and records none; the second traces
    its join through the cached index (a dense table at this size): one
    ``join`` event, ``live`` never; the third runs the compiled stage."""
    first, second, third = stages[2]
    assert first == [] and third == []
    assert [(e["rung"], e["how"], e["orient"]) for e in second] == [
        ("table", "inner", "fwd")]
    assert second[0]["build_rows"] > 0 and second[0]["probe_cap"] > 0


def test_build_events_are_registered_and_linted():
    assert {"seg_sum", "join", "sort"} <= trace.BUILD_EVENTS
    sys.path.insert(0, TOOLS)
    try:
        import lint_invariants
    finally:
        sys.path.remove(TOOLS)
    out = []
    lint_invariants._check_span_names(
        ast.parse("trace.built('bogus', rows=1)\n"
                  "_trace.built('join', rung='live')\n"
                  "trace.span('not.a.span')"), "x.py", out)
    assert [(f.rule, f.line) for f in out] == [("span-names", 1),
                                               ("span-names", 3)]
    assert "BUILD_EVENTS" in out[0].message
    assert "SPAN_NAMES" in out[1].message
