"""Regenerate golden results: ``python -m tests.sql_golden.regen``.

sqlite-oracled files run against sqlite3 (independent implementation);
``-- oracle: engine`` files run against the engine itself (regression
locks, matching the reference's self-generated goldens)."""

from __future__ import annotations

import os
import sys

from tests.sql_golden import harness as H


def main() -> int:
    import jax

    # goldens are platform-independent; CPU avoids cold TPU compiles
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from spark_tpu.api.session import SparkSession

    spark = SparkSession.builder.getOrCreate()
    H.setup_engine(spark)
    conn = H.setup_sqlite()

    failures = 0
    for fname in H.input_files():
        oracle, stmts = H.parse_input(os.path.join(H.INPUTS, fname))
        entries = []
        for sql in stmts:
            try:
                if oracle == "engine":
                    rows = H.run_engine(spark, sql)
                else:
                    rows = H.run_sqlite(conn, sql)
                entries.append((sql, rows))
            except Exception as e:  # noqa: BLE001
                print(f"[regen] {fname}: {type(e).__name__}: {e}\n  {sql}",
                      file=sys.stderr)
                failures += 1
        out = os.path.join(H.GOLDENS, fname[:-4] + ".out")
        H.write_golden(out, entries)
        print(f"[regen] {fname}: {len(entries)} queries ({oracle})")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
