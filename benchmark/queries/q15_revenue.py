"""TPC-H Q15's aggregate (top supplier, clause 2.4.15): the view
``revenue[STREAM_ID]`` inlined as a derived table under the query's own
subquery ``select max(total_revenue)``, validation literal DATE =
1996-01-01. The join to supplier and the outer select are left out (the
configuration file says why). New in PR 35, in the style of q14.py."""

from reference import days, frame, money

TABLES = ("lineitem",)


def _refuse_a_program_that_cannot_finish():
    """The one thing this file asks of the program, and not for its
    answer: a program that runs the first execution of a sort-based
    aggregate operation by operation (every program before PR 35) spends
    2,093 s there at SF10 on the v5e from an empty compile cache, some 550
    programs compiled one by one (my chip run, PR 35; PERF.md section 6),
    and a run of this cell is stopped at 1,200 s. It is refused here, at
    once and with a non-zero exit code, so that a check measures the cell
    on the programs that can run it. Such a program is known by the one
    name this cell's own yardstick already holds still: the ``group_by``
    build event that ``group_agg_roofline_pct`` reads came with the
    compiled count, so no program has the one without the other, and
    nothing a refactor may rename is looked at. The next ``benchmark`` PR
    takes this function out (ROADMAP.md, W3): by then no program without
    the compiled count can be a parent."""
    from spark_tpu import trace

    if "group_by" not in trace.BUILD_EVENTS:
        raise SystemExit(
            "benchmark: q15_revenue needs a program that counts the groups "
            "of a sort-based aggregate in a compiled stage (PR 35; it "
            "records the group_by build event this cell's metrics read): "
            "without it the first execution compiles for 2,093 s at SF10 "
            "on the v5e (PERF.md, PR 35), past a run's 1,200 s")


_refuse_a_program_that_cannot_finish()


def _quarter(path):
    li = frame(path, "lineitem", ["l_suppkey", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    return li[(li.l_shipdate >= days(1996, 1, 1))
              & (li.l_shipdate < days(1996, 4, 1))]


def _revenue(path):
    """total_revenue by supplier_no, exact integers in 1e-4 units."""
    li = _quarter(path)
    return (li.l_extendedprice * (100 - li.l_discount)).groupby(
        li.l_suppkey).sum()


def reference(path):
    return [(money(_revenue(path).max(), 4),)]


def hbm_bytes(path):
    """Both date bounds are pushed, so one quarter of lineitem is resident
    and read once: the supplier key and two decimals, each of which fits
    int32 (4 + 4 + 4 B a row; the ship date is not on the device); and one
    exact int64 sum a supplier is written before the max reads it."""
    return len(_quarter(path)) * (4 + 4 + 4) + len(_revenue(path)) * 8
