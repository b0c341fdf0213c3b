"""The whole of a run, end to end, at SF0.01 on the CPU: one local cell and,
on four virtual devices, the mesh cell; what is refused by name; and that a
new cell, mix, query and per-layer metric need new files and entries only.

Each run is a process of its own, as the driver's are, through the
test-only entry tests/rehearse.py, over a copy of the benchmark's data files
in a temp directory whose configurations are cut to SF0.01 and whose table
of peaks knows the CPU. Nothing is written into the repo. The numbers are
CPU numbers and are thrown away: only their names and shape are checked.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
CPU_CANNOT = {"hbm_peak_mb"}     # XLA:CPU has no memory_stats()


def build_root(tmp: str) -> str:
    root = os.path.join(tmp, "root")
    os.makedirs(os.path.join(root, "benchmark"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "queries", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, d),
                        os.path.join(root, "benchmark", d))
    for name in os.listdir(os.path.join(root, "benchmark", "configs")):
        edit(os.path.join(root, "benchmark", "configs", name),
             lambda c: c.update(scale_factor=0.01))
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="rehearsal only")
    with open(os.path.join(root, "benchmark", "peaks.json"), "w") as f:
        json.dump(peaks, f)
    return root


def edit(path: str, change) -> None:
    with open(path) as f:
        obj = json.load(f)
    change(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("rehearsal"))


@pytest.fixture(scope="module")
def root(tmp):
    return build_root(tmp)


def rehearse(root, tmp, workload, trace, devices=1, seed=2**31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", SPARK_TPU_JAX_CACHE="0",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), root,
         os.path.join(tmp, "data"), "--workload", workload, "--seed",
         str(seed), "--seconds", "1", "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def names(root, workload, group):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    return {m["name"]: m["unit"] for m in spec[group]
            if workload in m.get("workloads", [workload])}


def check(root, result, workload, trace):
    assert set(result) == KEYS | ({"breakdown"} if trace else set())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    want = names(root, workload, "per_layer" if trace else "end_to_end")
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert {k: u for k, u in want.items() if k not in CPU_CANNOT} == got
    for m in result["metrics"].values():
        assert isinstance(m["value"], (int, float))
    device = result["device"]
    assert device["platform"] == "cpu"
    if trace:
        assert set(device) == DEVICE_KEYS | {"busy_s", "window_s"}
        assert 0 < device["busy_s"] <= device["window_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        for entries in result["breakdown"].values():
            assert 0 < len(entries) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in entries)
        assert result["metrics"]["window_compiles"]["value"] == 0
    else:
        assert set(device) == DEVICE_KEYS
        assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("trace", [0, 1])
def test_local_cell(root, tmp, trace):
    check(root, last_line(rehearse(root, tmp, "tpch_sf1_q6", trace)),
          "tpch_sf1_q6", trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_mesh_cell_on_four_virtual_devices(root, tmp, trace):
    result = last_line(rehearse(root, tmp, "tpch_sf1_mesh4_q1", trace,
                                devices=4))
    check(root, result, "tpch_sf1_mesh4_q1", trace)
    assert result["device"]["count"] == 4
    if trace:
        assert 25.0 <= result["metrics"]["hbm_max_share_pct"]["value"] <= 100


def test_the_join_cell_is_one_entry_away(tmp):
    """Q14's files are in the benchmark; its cell is not (PERF.md, Open
    questions: it has not run on the chip). Adding it is an entry."""
    root = build_root(os.path.join(tmp, "join"))

    def add(spec):
        spec["workloads"].append({
            "name": "tpch_sf1_q14", "config": "tpch_sf1_local",
            "traffic": "repeat_q14", "chips": 1, "why": "the join path"})
        for m in spec["end_to_end"]:
            if m["name"] == "query_p95_ms":
                m["workloads"].append("tpch_sf1_q14")

    edit(os.path.join(root, "BENCHMARK.json"), add)
    result = last_line(rehearse(root, tmp, "tpch_sf1_q14", 0))
    check(root, result, "tpch_sf1_q14", 0)
    assert "query_p95_ms" in result["metrics"]


def test_a_second_seed_gives_other_rows_and_the_same_shapes(root, tmp):
    import harness
    import tpch_gen

    paths = [tpch_gen.ensure_dataset(os.path.join(tmp, "data"), 0.01, seed,
                                     20260729) for seed in (2**31 + 7, 12)]
    for name in ("q1", "q6", "q14"):
        q = harness.Query(BENCH, name)
        rows = []
        for path in paths:
            q.prepare(path)
            rows.append(q.want)
        assert rows[0] != rows[1], name
        assert len(rows[0]) == len(rows[1]), name


def test_too_few_chips_is_refused(root, tmp):
    proc = rehearse(root, tmp, "tpch_sf1_mesh4_q1", 0, devices=1)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs 4 chip" in proc.stderr


def test_unknown_cell_is_refused_by_name(root, tmp):
    proc = rehearse(root, tmp, "tpch_sf1_q99", 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "tpch_sf1_q99" in proc.stderr


def test_unknown_traffic_kind_and_device_kind_are_refused_by_name(tmp):
    root = build_root(os.path.join(tmp, "refusals"))
    traffic = os.path.join(root, "benchmark", "traffic", "repeat_q6.json")
    edit(traffic, lambda t: t.update(loop="open", rate=100))
    proc = rehearse(root, tmp, "tpch_sf1_q6", 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "'open'" in proc.stderr and "repeat_q6" in proc.stderr
    edit(traffic, lambda t: t.update(loop="closed"))
    edit(os.path.join(root, "benchmark", "peaks.json"),
         lambda p: p.pop("cpu"))
    proc = rehearse(root, tmp, "tpch_sf1_q6", 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "device_kind 'cpu'" in proc.stderr


def test_the_command_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tpch_sf1_q6", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_a_new_cell_needs_new_files_and_entries_only(tmp):
    """A throw-away cell: a new configuration, a new mix of two queries (one
    of them new, with its reference), and a new per-layer metric, all as
    files beside the copies; BENCHMARK.json gains entries and loses none."""
    root = build_root(os.path.join(tmp, "throwaway"))
    bench = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(bench, "configs", "tpch_sf1_local.json"),
                os.path.join(bench, "configs", "tiny_local.json"))
    with open(os.path.join(bench, "traffic", "two_queries.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 2,
                   "queries": ["q6", "nations"], "order": "round_robin",
                   "literals": "validation"}, f)
    with open(os.path.join(bench, "queries", "nations.sql"), "w") as f:
        f.write("select r_name, count(*) as n from nation, region "
                "where n_regionkey = r_regionkey group by r_name "
                "order by r_name\n")
    with open(os.path.join(bench, "queries", "nations.py"), "w") as f:
        f.write(
            "from reference import frame\n"
            "TABLES = ('nation', 'region')\n"
            "ORDERED = True\n"
            "def reference(path):\n"
            "    n = frame(path, 'nation', ['n_regionkey'])\n"
            "    r = frame(path, 'region', ['r_regionkey', 'r_name'])\n"
            "    j = n.merge(r, left_on='n_regionkey',\n"
            "                right_on='r_regionkey')\n"
            "    g = j.groupby('r_name').size().sort_index()\n"
            "    return [(str(k), int(v)) for k, v in g.items()]\n"
            "def hbm_bytes(path):\n"
            "    return 25 * 8 + 5 * (8 + 4)\n")
    with open(os.path.join(bench, "layer_metrics", "slice_executions.py"),
              "w") as f:
        f.write("def read(ctx):\n    return len(ctx['executions'])\n")

    def add(spec):
        spec["configs"].append(dict(spec["configs"][0], name="tiny_local",
                                    file="benchmark/configs/tiny_local.json"))
        spec["workloads"].append({
            "name": "tiny_two", "config": "tiny_local",
            "traffic": "two_queries", "chips": 1, "why": "throw-away"})
        spec["per_layer"].append({
            "name": "slice_executions", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "entry / SQL",
            "moves": "rows_per_s", "workloads": ["tiny_two"]})

    edit(os.path.join(root, "BENCHMARK.json"), add)
    for trace in (0, 1):
        result = last_line(rehearse(root, tmp, "tiny_two", trace))
        check(root, result, "tiny_two", trace)
    assert result["metrics"]["slice_executions"]["value"] >= 2
    # the cells that were there still run from the same root, untouched
    assert "slice_executions" not in names(root, "tpch_sf1_q6", "per_layer")
