"""The system under test for configuration files with ``"driver":
"tpch_q5"``: six tables resident on the chip in buffers of the
configuration's ``table_capacity``, and one query = the logical plan of
TPC-H Q5 built for the query's REGION and DATE, handed to
``LogicalPlan.execute()`` as it stands and fetched to the host as NumPy
columns.  What the optimiser reorders or fuses is the program's business."""
from __future__ import annotations

from bench.drivers.join_gbs import fetch, modes  # noqa: F401  (one program)

from cylon_tpu.obs import metrics as _metrics
from cylon_tpu.plan import expr as _expr

if not hasattr(_expr.Expr, "literals"):
    # The check of a PR lays the benchmark's files over the parent commit
    # and tries every new cell there first.  A program that runs a plan's
    # stages as eager primitives on one shard compiles about a hundred
    # programs for the first cycle, two of them for ten minutes each
    # (PERF.md Findings PR 31): its first run is killed at its limit, and
    # a killed parent refuses the change.  It cannot run this
    # configuration; say so at once.
    raise SystemExit("tpch_q5: this program has no stage programs with "
                     "literal operands (cylon_tpu.plan.expr.Expr.literals): "
                     "it cannot run the configuration")

#: stage programs a query launches at the least: the date range,
#: c_nationkey = s_nationkey, the last join's count, the fused stage
STAGES_A_QUERY = 4


def build(ctx, cfg: dict, data: dict) -> dict:
    from cylon_tpu import Table

    state = {name: Table.from_numpy(list(cols), list(cols.values()), ctx=ctx,
                                    capacity=cfg["table_capacity"][name])
             for name, cols in data.items()}
    state["ctx"] = ctx
    state["stage_programs"] = []  # of each query run, warm-up included
    return state


def plan(state: dict, query: dict):
    """Six tables, five joins, the date range, c_nationkey = s_nationkey,
    the region by its name, the revenue, the group-by on n_name, the
    order: the joins in the order of ``git show
    091b6b8:bench/drivers/tpch_q5.py`` (ISSUE 31; the optimiser reorders no
    join), the nation -> region join last and n_regionkey among the group
    keys, so that on many shards the planner can elide the last shuffle."""
    from cylon_tpu.plan import col, lit

    return (state["customer"].plan()
            .join(state["orders"].plan()
                  .filter((col("o_orderdate") >= query["date_lo"])
                          & (col("o_orderdate") < query["date_hi"])),
                  left_on="c_custkey", right_on="o_custkey")
            .join(state["lineitem"].plan(), left_on="o_orderkey",
                  right_on="l_orderkey")
            .join(state["supplier"].plan(), left_on="l_suppkey",
                  right_on="s_suppkey")
            .filter(col("c_nationkey") == col("s_nationkey"))
            .join(state["nation"].plan(), left_on="c_nationkey",
                  right_on="n_nationkey")
            .join(state["region"].plan(), left_on="n_regionkey",
                  right_on="r_regionkey")
            .filter(col("r_name") == query["region"])
            .with_column("revenue",
                         col("l_extendedprice") * (lit(1.0)
                                                   - col("l_discount")))
            .groupby(["n_regionkey", "n_name"], {"revenue": ["sum"]})
            .project(["n_name", "sum_revenue"])
            .sort(["sum_revenue", "n_name"], ascending=[False, True]))


def run(state: dict, query: dict):
    before = _metrics.counter_value("plan.stage_programs")
    out = plan(state, query).execute()
    state["stage_programs"].append(
        _metrics.counter_value("plan.stage_programs") - before)
    return out


def structure(state: dict, chips: int, counters: dict) -> dict:
    """What a comparison of answers cannot see: that every query went
    through the planner's stage programs.  A count that has to be 0."""
    return {"queries_without_stage_programs": sum(
        1 for launched in state["stage_programs"]
        if launched < STAGES_A_QUERY)}
