"""Chip probe behind ShardedBatch.to_batch (PR 34; PERF.md section 6).

What it costs to turn a finished mesh result (10 columns, validities and
a row mask, each sharded over the chips along axis 0) into the
one-device batch ``Batch.fetch_host`` takes, and to bring it to the host,
on the attached chips, for a 512-row result and a 1 M-row one:

  chiprun --chips 4 -- python tools/probe_mesh_gather.py   # ~3 min

  per_array     np.asarray on every sharded array, jnp.asarray back
                (to_batch before PR 34)
  replicated    ONE jitted program with replicated outputs (XLA's
                all-gather), then the first device's shard of each
                (holds D copies of the result; not shipped)
  host_batched  every shard's copy to the host started at once, numpy
                assembly, ONE device_put of the tree to the first device
                (to_batch since PR 34: for a consumer of device arrays,
                at every size)
  device_put    jax.device_put of the sharded tree to the first device
  planes        packed ON the mesh into sharded int64 / float64 planes,
                fetched shard by shard: ends on the host, makes no batch
                (MeshResult.fetch_host since PR 34: the probe's choice)

``gather_ms`` is the variant alone, ``reps`` executions enqueued and
waited for once (pipelined); ``ms`` the median of one execution through
to host numpy (the first four then pack on the first device as
``fetch_host`` does). Every execution gathers fresh arrays (a jax array
keeps its host copy once made), and every variant's host result is
checked equal to ``per_array``'s. Each line of output is one JSON object;
``tools/probe_mesh_gather.v5e_2x2.jsonl`` holds PR 34's run.
Refuses to run without a TPU (a CPU time is not a device time).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

ROWS = (512, 1 << 20)
# Q1's result: two dictionary-coded strings, exact decimal sums and a
# count as int64, two float averages; all but the keys nullable
DTYPES = ("int32", "int32") + ("int64",) * 6 + ("float64",) * 2
NULLABLE = (False, False) + (True,) * 8


def _pack(arrays):
    """``Batch.fetch_host``'s packer: an int64 and a float64 plane."""
    ints = [x.astype(jnp.int64) for x in arrays
            if not jnp.issubdtype(x.dtype, jnp.floating)]
    flts = [x.astype(jnp.float64) for x in arrays
            if jnp.issubdtype(x.dtype, jnp.floating)]
    return jnp.stack(ints), jnp.stack(flts)


def _to_host(planes):
    for x in planes:
        x.copy_to_host_async()
    return [np.asarray(x) for x in planes]


def variants(mesh):
    """name -> fn(sharded arrays) -> one-device arrays (``planes``: the
    sharded planes themselves)."""
    first = mesh.devices.flat[0]
    replicate = jax.jit(lambda *xs: xs,
                        out_shardings=NamedSharding(mesh, P()))
    pack_sharded = jax.jit(
        _pack, out_shardings=NamedSharding(mesh, P(None, "data")))

    def per_array(xs):
        return [jnp.asarray(np.asarray(x)) for x in xs]

    def replicated(xs):
        return [x.addressable_data(0) for x in replicate(*xs)]

    def host_batched(xs):
        for x in xs:
            x.copy_to_host_async()
        return jax.device_put([np.asarray(x) for x in xs], first)

    def device_put(xs):
        return jax.device_put(list(xs), first)

    return {"per_array": per_array, "replicated": replicated,
            "host_batched": host_batched, "device_put": device_put,
            "planes": pack_sharded}


def probe(mesh, rows, reps):
    """Print a line a variant; the number that failed or differ."""
    d = mesh.devices.size
    rng = np.random.default_rng(34)
    base = [rng.integers(0, 1 << 20, rows).astype(dt) for dt in DTYPES]
    base += [rng.random(rows) < 0.9 for null in NULLABLE if null]
    base.append(rng.random(rows) < 0.98)    # the row mask
    sharded = NamedSharding(mesh, P("data"))
    base_dev = [jax.device_put(x, sharded) for x in base]
    # a stage's stand-in: new arrays every execution
    fresh = jax.jit(lambda xs: [jnp.copy(x) for x in xs],
                    out_shardings=sharded)
    pack_one = jax.jit(_pack)
    want = None
    bad = 0
    for name, fn in variants(mesh).items():
        line = {"rows": rows, "chips": d, "arrays": len(base),
                "bytes": sum(x.nbytes for x in base), "variant": name}
        try:
            def to_host(xs, name=name, fn=fn):
                out = fn(xs)
                return _to_host(out if name == "planes" else pack_one(out))

            t0 = time.perf_counter()
            got = to_host(fresh(base_dev))
            line["first_s"] = round(time.perf_counter() - t0, 2)
            inputs = jax.block_until_ready(
                [fresh(base_dev) for _ in range(2 * reps)])
            each = []
            for xs in inputs[:reps]:
                t0 = time.perf_counter()
                to_host(xs)
                each.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            jax.block_until_ready([fn(xs) for xs in inputs[reps:]])
            piped = (time.perf_counter() - t0) * 1e3 / reps
            del inputs
        except Exception as e:
            line["error"] = f"{type(e).__name__}: {e}"[:300]
            equal = False
        else:
            if want is None:
                want = got
            equal = all(np.array_equal(g, w) for g, w in zip(got, want))
            line.update(ms=round(statistics.median(each), 3),
                        gather_ms=round(piped, 3), equal=equal)
        print(json.dumps(line), flush=True)
        bad += not equal
    return bad


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, action="append")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: times mean nothing")
    a = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu" and not a.allow_cpu:
        print("probe_mesh_gather: no TPU", file=sys.stderr)
        return 2
    mesh = Mesh(np.array(devices), ("data",))
    print(json.dumps({"device": devices[0].device_kind,
                      "chips": len(devices)}))
    return 1 if sum(probe(mesh, n, a.reps) for n in a.rows or ROWS) else 0


if __name__ == "__main__":
    sys.exit(main())
