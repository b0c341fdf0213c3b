"""Cross-session executable store.

The jit stage caches (physical/planner._STAGE_CACHE,
parallel/executor._DIST_STAGE_CACHE) die with the process; every fresh
session pays the full XLA compile again — 12-55 s of warmup against a
~100 ms steady state. This store persists the compiled stage
executables themselves, keyed by a *cross-process-stable* plan
fingerprint plus a capacity/mesh/device-kind environment fingerprint,
so a worker restart loads AOT artifacts instead of compiling
(jax.experimental.serialize_executable round-trips a
``jax.stages.Compiled``; the reference analogue is reusing
Janino-compiled classes, CodeGenerator.scala:1442 — taken across
processes, the Flare move of treating the executable as the product).

Why not reuse ``plan_key()`` directly: it embeds ``hash(dicts)`` for
dictionary-encoded string columns, and Python string hashes are salted
per process — fine for the in-process LRU, useless on disk. The walker
here mirrors plan_key's structure but digests dictionary *contents*
(memoized per schema — the digest is only computed on the store path,
never on the per-query hot path).

Corruption policy: any failure to read/unpickle/deserialize an entry is
a cache miss AND evicts the file — a poisoned entry must not wedge
every future session (the jax persistent cache had exactly this bug;
see api/session._harden_cache_writes).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import threading
import weakref
from typing import Any, Optional, Sequence, Tuple

import jax

from spark_tpu import locks
from spark_tpu import metrics

_ENTRY_SUFFIX = ".exe"

#: process-global map (store_root, digest) -> loaded entry dict, so a
#: second Session over the same store dir in one process skips even the
#: disk read/deserialize. Tests clear it to force the disk path.
_LOADED: dict = {}
_LOADED_LOCK = locks.named_lock("compile.loaded")


# ---- stable plan fingerprint ------------------------------------------------

#: schema -> dictionary-contents digest, memoized per schema object:
#: TPC-H comment columns carry multi-million-entry dictionaries and the
#: digest must not be recomputed per lookup
_DICT_FP: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_DICT_FP_LOCK = locks.named_lock("compile.dict_fp")


def _dict_digest(schema) -> str:
    with _DICT_FP_LOCK:
        fp = _DICT_FP.get(schema)
    if fp is not None:
        return fp
    h = hashlib.sha1()
    for f in schema.fields:
        h.update(b"\x00")
        d = getattr(f, "dictionary", None)
        if d:
            for s in d:
                h.update(str(s).encode("utf-8", "replace"))
                h.update(b"\x01")
    fp = h.hexdigest()[:16]
    try:
        with _DICT_FP_LOCK:
            _DICT_FP[schema] = fp
    except TypeError:
        pass  # unweakrefable schema type: recompute next time
    return fp


def _leaf_key(plan) -> Optional[tuple]:
    """Stable identity for the two leaf scan node types (the unstable
    ``hash(dicts)`` component of their plan_key is replaced by a
    content digest)."""
    from spark_tpu.physical.operators import scan_plan_key

    batch = getattr(plan, "batch", None)
    if batch is not None and hasattr(batch, "schema") \
            and hasattr(batch, "capacity"):
        return scan_plan_key("BatchScan", int(batch.capacity), batch.schema,
                             batch.data, _dict_digest(batch.schema))
    sharded = getattr(plan, "sharded", None)
    if sharded is not None:
        return scan_plan_key("ShardScan", int(sharded.per_device_capacity),
                             sharded.schema, sharded.data,
                             _dict_digest(sharded.schema))
    return None


def _canon(v) -> Any:
    """Deterministic, repr-able canonical form of a plan-key component.
    Unknown objects collapse to their type name — that can only *widen*
    a key into a false miss, never alias two different plans that the
    structural components distinguish."""
    from spark_tpu.expr import expressions as E
    from spark_tpu.physical import operators as P

    if isinstance(v, P.PhysicalPlan):
        return stable_plan_key(v)
    if isinstance(v, E.Expression):
        return _canon(E.expr_key(v))
    if isinstance(v, (tuple, list)):
        return tuple(_canon(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((repr(k), _canon(x)) for k, x in v.items()))
    if v is None or isinstance(v, (str, bytes, bool, int, float)):
        return repr(v)
    item = getattr(v, "item", None)
    if callable(item):
        try:
            return repr(item())  # numpy scalars
        except Exception:
            pass
    return f"<{type(v).__name__}>"


def stable_plan_key(plan) -> tuple:
    """Cross-process-stable structural key of a physical plan: mirrors
    ``plan_key()`` (type + field values + children) with content
    digests at data leaves."""
    lk = _leaf_key(plan)
    if lk is not None:
        return lk
    parts: list = [type(plan).__name__]
    if dataclasses.is_dataclass(plan):
        for f in dataclasses.fields(plan):
            parts.append(_canon(getattr(plan, f.name)))
    else:
        parts.append(_canon(getattr(plan, "plan_key", lambda: repr(plan))()))
    return tuple(parts)


def _args_signature(args) -> tuple:
    """Treedef + leaf avals of the stage arguments — part of the store
    key (a deserialized executable is shape- and structure-specialized;
    same plan with different validity layout must be a different
    entry)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (repr(treedef),
            tuple((tuple(getattr(leaf, "shape", ())),
                   str(getattr(leaf, "dtype", type(leaf).__name__)))
                  for leaf in leaves))


def environment_fingerprint(mesh_size: int = 1,
                            platform: Optional[str] = None) -> tuple:
    """Capacity lives in the plan key (leaf capacities); this adds the
    mesh/device-kind half: device kind + count, backend platform, jax
    version, and x64 mode (an AOT executable is specialized to all of
    them)."""
    try:
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "unknown")
        plat = platform or dev.platform
    except Exception:
        kind, plat = "unknown", platform or "unknown"
    return (plat, kind, int(mesh_size), jax.__version__,
            bool(jax.config.jax_enable_x64))


def stable_plan_fingerprint(tier: str, plan, args, *, mesh_size: int = 1,
                            platform: Optional[str] = None,
                            extra: Any = None) -> str:
    """Hex digest identifying one stage executable across sessions and
    processes: stable plan structure + argument avals + environment.

    ``extra`` carries tier-specific compilation parameters that live
    outside the plan tree: the ``fused_span`` tier (whole-query
    fusion) passes one ``("ladder", bucket, variants)`` tuple per
    fused span, so executables whose lax.switch branch set differs —
    a changed ``spark.tpu.adaptive.capacityBucket`` or
    ``spark.tpu.fusion.maxBucketVariants`` — never replay each
    other's binaries, while prewarm replays exact matches."""
    payload = (tier, stable_plan_key(plan), _args_signature(args),
               environment_fingerprint(mesh_size, platform),
               _canon(extra))
    return hashlib.sha256(repr(payload).encode()).hexdigest()[:32]


def _default_device():
    """The device an uncommitted single-device computation runs on."""
    dev = jax.config.jax_default_device
    return dev if isinstance(dev, jax.Device) else jax.local_devices()[0]


# ---- the store --------------------------------------------------------------


class ExecutableStore:
    """Disk-backed executable cache with a byte bound and LRU eviction.

    Layout under ``root``::

        entries/<digest>.exe   pickled {payload, in_tree, out_tree,
                               schema, sig} — payload is the serialized
                               XLA executable
        plan_history.jsonl     served-plan history (service owns it)

    Writes are atomic (temp + rename); loads treat ANY failure as a
    miss and evict the entry. Eviction order is file mtime — hits touch
    their entry, so mtime is last-use."""

    def __init__(self, root: str, max_bytes: int = 1 << 30):
        self.root = os.path.abspath(root)
        self.entries_dir = os.path.join(self.root, "entries")
        self.max_bytes = int(max_bytes)
        os.makedirs(self.entries_dir, exist_ok=True)
        self._lock = locks.named_lock("compile.store")

    # -- paths

    def _entry_path(self, digest: str) -> str:
        return os.path.join(self.entries_dir, digest + _ENTRY_SUFFIX)

    # -- read side

    def load(self, digest: str, args,
             devices: Optional[Sequence] = None) -> Optional[dict]:
        """Return {"compiled", "schema", "sig"} for a stored executable
        whose argument signature matches ``args``, or None. Corrupt or
        mismatched-structure entries are evicted as misses.

        ``devices`` are the devices the executable runs on: a mesh
        stage passes its mesh's, a single-device stage leaves None for
        the default device. (jax's own default is every device of the
        backend, which turns a one-device program into an N-shard one
        as soon as N > 1 devices are visible.)"""
        with _LOADED_LOCK:
            cached = _LOADED.get((self.root, digest))
        if cached is not None:
            return cached
        path = self._entry_path(digest)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                entry = pickle.loads(f.read())
            if entry.get("sig") != _args_signature(args):
                raise ValueError("argument signature mismatch")
            from jax.experimental import serialize_executable as _se

            compiled = _se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=list(devices) if devices is not None
                else [_default_device()])
        except Exception as e:
            # treat as a miss AND evict: a poisoned entry must not
            # wedge every future session
            metrics.note_exec_store("corrupt")
            metrics.record("compile", phase="corrupt_entry",
                           digest=digest, error=repr(e))
            self._remove(path)
            return None
        out = {"compiled": compiled, "schema": entry["schema"],
               "sig": entry["sig"]}
        try:
            os.utime(path)  # LRU touch
        except OSError:
            pass
        with _LOADED_LOCK:
            _LOADED[(self.root, digest)] = out
        return out

    # -- write side

    def put(self, digest: str, compiled, schema, args) -> bool:
        """Serialize ``compiled`` to disk (atomic); False when the
        platform refuses to serialize (entry stays process-local)."""
        try:
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps({
                "payload": payload, "in_tree": in_tree,
                "out_tree": out_tree, "schema": schema,
                "sig": _args_signature(args),
            }, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            metrics.record("compile", phase="serialize_failed",
                           digest=digest, error=repr(e))
            return False
        path = self._entry_path(digest)
        tmp = f"{path}.tmp{os.getpid()}-{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError as e:
            metrics.record("compile", phase="put_failed",
                           digest=digest, error=repr(e))
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        with _LOADED_LOCK:
            _LOADED[(self.root, digest)] = {
                "compiled": compiled, "schema": schema,
                "sig": _args_signature(args)}
        metrics.note_exec_store("puts")
        self.enforce_budget()
        return True

    def _remove(self, path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    # -- size bound

    def _walk_files(self):
        for dirpath, _dirnames, filenames in os.walk(self.root):
            for name in filenames:
                if name.endswith(".jsonl") or ".tmp" in name:
                    continue  # history + in-flight writes are exempt
                p = os.path.join(dirpath, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                yield p, st.st_size, st.st_mtime

    def total_bytes(self) -> int:
        return sum(size for _p, size, _m in self._walk_files())

    def entry_count(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.entries_dir)
                       if n.endswith(_ENTRY_SUFFIX))
        except OSError:
            return 0

    def enforce_budget(self) -> int:
        """Evict least-recently-used files (ours AND the managed jax
        cache subdir) until the store fits max_bytes; returns evicted
        count. Serialized under a lock — concurrent enforcement would
        double-delete."""
        with self._lock:
            files = sorted(self._walk_files(), key=lambda t: t[2])
            total = sum(size for _p, size, _m in files)
            evicted = 0
            while total > self.max_bytes and files:
                path, size, _mtime = files.pop(0)
                self._remove(path)
                total -= size
                evicted += 1
                digest = os.path.basename(path)[:-len(_ENTRY_SUFFIX)] \
                    if path.endswith(_ENTRY_SUFFIX) else None
                if digest is not None:
                    with _LOADED_LOCK:
                        _LOADED.pop((self.root, digest), None)
        if evicted:
            metrics.note_exec_store("evictions", evicted)
            metrics.record("compile", phase="evict", count=evicted,
                           bytes_after=total)
        metrics.set_gauge("compile.store.bytes", total)
        metrics.set_gauge("compile.store.entries", self.entry_count())
        return evicted

    def stats(self) -> dict:
        return {
            "root": self.root,
            "entries": self.entry_count(),
            "bytes": self.total_bytes(),
            "max_bytes": self.max_bytes,
            "loaded_in_process": sum(
                1 for (root, _d) in _LOADED if root == self.root),
        }


def clear_process_cache() -> None:
    """Drop the in-process loaded-executable registry (tests use this
    to force the disk deserialize path, simulating a fresh process)."""
    with _LOADED_LOCK:
        _LOADED.clear()


def compiled_call_signature(args) -> Tuple[Any, ...]:
    """Public alias used by the service's hybrid callable to cheaply
    check per-call argument compatibility with a Compiled."""
    return _args_signature(args)
