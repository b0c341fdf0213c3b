"""Planner, blocking run: XLA sorts the program built in set-up, one
``sort`` build event each (spark_tpu/physical/kernels.py: the sites
``join_index``, ``searchsorted`` by co-sort, ``lexsort``, ``compaction``),
eager in the blocking first run or inside a stage's trace. On the chip's
compiler a cold sort program costs 22-69 s (ROADMAP A2), so this is what
``first_exec_s`` multiplies. A program that records no such event (the
parent of PR 28) reads ``None``."""


def read(ctx):
    return sum(1 for e in ctx["setup_events"] if e["kind"] == "sort") or None
