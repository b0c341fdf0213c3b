"""The stage builder both engines share: a maximal traceable subtree of
physical operators becomes ONE traced function over its leaves' data.

The one-chip planner (``physical/planner.py::_run_fused``) and the mesh
executor (``parallel/executor.py::_run_stage_inner``) differ in their
leaf type (``BatchScanExec`` / ``ShardScanExec``), their stage cache and
its key, and what they do round the call; what a stage IS — which
subtree qualifies, how its leaves are found, how a cached closure is
kept from pinning leaf buffers, how the operators are traced — is here,
once.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple, Type

from spark_tpu import trace
from spark_tpu.physical import operators as P
from spark_tpu.types import Schema


def fully_traceable(plan: P.PhysicalPlan, leaf_type: Type) -> bool:
    if isinstance(plan, leaf_type):
        return True
    return (plan.traceable and not plan.has_blocking_exprs()
            and all(fully_traceable(c, leaf_type) for c in plan.children()))


def collect_leaves(plan: P.PhysicalPlan, leaf_type: Type,
                   out: Optional[List] = None) -> List:
    """The stage's leaves in tree order: the order ``build_stage``'s
    function takes their data in."""
    out = [] if out is None else out
    if isinstance(plan, leaf_type):
        out.append(plan)
    else:
        for c in plan.children():
            collect_leaves(c, leaf_type, out)
    return out


@dataclass(eq=False)
class _LeafSlot(P.PhysicalPlan):
    """Leaf placeholder in cached stage closures: carries only the scan
    schema so cached jit functions never pin leaf device buffers."""

    scan_schema: Schema
    traceable = True

    @property
    def schema(self):
        return self.scan_schema


def strip_leaves(plan: P.PhysicalPlan, leaf_type: Type) -> P.PhysicalPlan:
    if isinstance(plan, leaf_type):
        return _LeafSlot(plan.schema)
    return plan.map_children(lambda c: strip_leaves(c, leaf_type))


def build_stage(tier: str, plan: P.PhysicalPlan, leaf_type: Type,
                example_args: Tuple, *, name: str,
                wrap: Optional[Callable[[Callable], Callable]] = None,
                **store_kw: Any) -> Tuple[Callable, dict]:
    """What a stage cache stores for a fresh entry: (callable over the
    leaves' data in ``collect_leaves`` order — ``example_args`` is this
    execution's — and the box its first trace leaves the output schema
    in). Runs on a cache miss only.

    ``name`` is the traced function's ``__name__`` and so the HLO
    module's name, part of the persistent compile cache's key. ``wrap``
    goes between the traced function and the jit (the mesh's
    ``shard_map``). ``store_kw`` are ``build_stage_callable``'s keywords
    for the cross-session executable store."""
    schema_box: dict = {}
    skeleton = strip_leaves(plan, leaf_type)

    def stage_fn(leaf_datas):
        it = iter(leaf_datas)

        def go(p: P.PhysicalPlan) -> P.Pipe:
            if isinstance(p, _LeafSlot):
                return P.Pipe.from_batch_data(p.scan_schema, next(it))
            pipes = [go(c) for c in p.children()]
            with trace.operator_scope(p):
                return p.trace(pipes)

        batch = go(skeleton).to_batch()
        schema_box["schema"] = batch.schema
        return batch.data

    stage_fn.__name__ = stage_fn.__qualname__ = name
    # the stored callable consults the cross-session executable store
    # when the compile service is active; otherwise it is exactly
    # jax.jit of the traced function
    from spark_tpu.compile import build_stage_callable

    fn = stage_fn if wrap is None else wrap(stage_fn)
    return build_stage_callable(tier, plan, fn, example_args, schema_box,
                                **store_kw), schema_box
