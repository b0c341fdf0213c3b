"""Test harness: a 'local-mesh' analogue of the reference's local[N] /
local-cluster[n,c,m] master URLs (reference: SparkContext master parsing;
LocalSparkCluster.scala) — 8 virtual CPU devices so distributed paths are
exercised without TPU hardware (SURVEY.md §4 'Lesson for the TPU build').

The suite runs on the CPU whatever the environment says: the platform
is pinned through jax.config below, before any backend initializes.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# XLA:CPU AOT executable (de)serialization aborts/segfaults
# nondeterministically deep into the full-suite process (see
# session._enable_compilation_cache); tests run without the disk cache.
os.environ.setdefault("SPARK_TPU_JAX_CACHE", "0")


def _raise_map_count_limit() -> None:
    """The full suite jit-compiles thousands of XLA programs in ONE
    process; each maps several executable/code regions, and the process
    blows through the default vm.max_map_count (65530) near the END of
    the run — mmap starts failing and XLA:CPU crashes (SIGSEGV/SIGABRT
    in compile/serialize/deserialize, diagnosed by watching
    /proc/<pid>/maps grow ~4k/min to the limit). Raise the limit when
    we can (root in CI images); otherwise leave a loud hint."""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            cur = int(f.read())
        if cur < 1 << 20:
            with open("/proc/sys/vm/max_map_count", "w") as f:
                f.write(str(1 << 21))
    except (OSError, ValueError):
        import warnings

        warnings.warn(
            "could not raise vm.max_map_count; the full suite may "
            "crash near the end when XLA mappings exhaust the limit "
            "(run: sysctl -w vm.max_map_count=2097152)")


_raise_map_count_limit()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from spark_tpu.api.session import _enable_compilation_cache  # noqa: E402

_enable_compilation_cache()  # no-op under SPARK_TPU_JAX_CACHE=0 (above)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (the FULL TPC-H both-engine sweep; "
             "the default selection keeps the suite under ~5 min while "
             "still covering every operator class)")


def pytest_configure(config):
    # registered here as well as pyproject.toml so ad-hoc invocations
    # with -p no:cacheprovider -o addopts= never warn on the marker
    config.addinivalue_line(
        "markers",
        "storage: HBM-resident columnar storage / unified memory "
        "manager tests (spark_tpu/storage/)")
    config.addinivalue_line(
        "markers",
        "aqe: adaptive query execution over the mesh — runtime "
        "shuffle stats, capacity re-planning, broadcast switching, "
        "skew splitting")
    config.addinivalue_line(
        "markers",
        "compile: AOT compilation service tests (spark_tpu/compile/) — "
        "executable store, background compile + hot-swap, pre-warm")
    config.addinivalue_line(
        "markers",
        "analysis: static plan analysis — shape/dtype/capacity oracle, "
        "recompilation hazards, transform legality, invariant + "
        "concurrency linters")
    config.addinivalue_line(
        "markers",
        "serve: scale-out serving tier (spark_tpu/serve/) — federation "
        "router, plan-keyed result cache, cross-replica shedding")
    config.addinivalue_line(
        "markers",
        "mview: incrementally-maintained materialized views "
        "(spark_tpu/mview/) — delta detection, re-merge, stream "
        "convergence, serve repopulation")
    config.addinivalue_line(
        "markers",
        "agg: runtime-adaptive aggregation — cardinality-sketched "
        "strategy switching (partial->final / bypass / hash-partial / "
        "sort / hot-key presplit), Count-Min heavy hitters, Pallas "
        "segmented reductions, byte-identity sweeps")
    config.addinivalue_line(
        "markers",
        "trace: end-to-end query tracing (spark_tpu/trace/) — "
        "hierarchical spans, cross-replica context propagation, "
        "Perfetto export, overhead guard")
    config.addinivalue_line(
        "markers",
        "chaos: seeded chaos-campaign harness (spark_tpu/chaos.py) — "
        "randomized multi-point fault schedules asserting "
        "byte-identical-or-typed-error, zero hangs, attempts within "
        "the unified retry budget")
    config.addinivalue_line(
        "markers",
        "slo: SLO-driven serving (spark_tpu/slo/) — per-plan latency "
        "prediction, EDF scheduling, reject-at-admission, predictive "
        "brownout, on/off byte-identity")
    config.addinivalue_line(
        "markers",
        "fusion: whole-query native fusion — on-device adaptive "
        "capacity decisions, single-XLA-program multi-stage spans, "
        "bucket-ladder branch selection, staged-fallback bailouts, "
        "on/off byte-identity")


def pytest_collection_modifyitems(config, items):
    # compile tests join daemon background-compile threads; every one
    # gets the SIGALRM deadlock guard so a wedged join fails instead of
    # hanging tier-1 (tests may still carry their own tighter timeout)
    for item in items:
        if ("compile" in item.keywords or "serve" in item.keywords
                or "mview" in item.keywords or "agg" in item.keywords
                or "trace" in item.keywords
                or "chaos" in item.keywords
                or "slo" in item.keywords
                or "fusion" in item.keywords) \
                and item.get_closest_marker("timeout") is None:
            item.add_marker(pytest.mark.timeout(300))
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: run with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Deadlock guard: ``@pytest.mark.timeout(S)`` fails a test after S
    seconds instead of hanging the whole tier-1 run (pytest-timeout is
    not in the image; SIGALRM interrupts even a blocking lock acquire
    on the main thread). Scheduler tests all carry it — a wedged queue
    must fail fast, not wedge CI."""
    import signal
    import threading

    marker = item.get_closest_marker("timeout")
    seconds = float(marker.args[0]) if marker and marker.args else 0.0
    if (seconds <= 0 or not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _on_timeout(signum, frame):
        pytest.fail(f"deadlock guard: test exceeded {seconds:g}s "
                    f"(likely a wedged queue or gate)")

    prev = signal.signal(signal.SIGALRM, _on_timeout)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def spark():
    from spark_tpu.api.session import SparkSession

    return SparkSession.builder.getOrCreate()


@pytest.fixture(params=["local", "mesh[4]"])
def engine(request, spark):
    """The session's single-device engine, or a mesh[4] session that
    leaves the suite's own session as it found it."""
    from spark_tpu.api.session import SparkSession

    if request.param == "local":
        yield spark
        return
    prev = SparkSession._active
    SparkSession._reset()
    yield SparkSession.builder.master(request.param).getOrCreate()
    SparkSession._reset()
    SparkSession._active = prev
