"""The system under test for configuration files with ``"driver":
"tpch_q5"``: six tables resident on the chip, and one query = the plan
of examples/tpch_q5.py::run_plan (copied), built for the query's REGION
and DATE, run with ``LogicalPlan.execute()`` and fetched to the host."""
from __future__ import annotations

# the same program: tables from host columns, answers as NumPy columns
from bench.drivers.join_gbs import build, fetch, modes  # noqa: F401


def run(state: dict, query: dict):
    """The nation->region join comes last and the group keys include
    n_regionkey, so the planner can elide the final shuffle and fuse the
    region probe, the filter, the revenue and the local aggregate."""
    from cylon_tpu.plan import col, lit

    plan = (state["customer"].plan()
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
            .filter(col("r_regionkey") == lit(query["region_key"]))
            .with_column("revenue",
                         col("l_extendedprice") * (lit(1.0)
                                                   - col("l_discount")))
            .groupby(["n_regionkey", "n_name"], {"revenue": ["sum"]})
            .project(["n_name", "sum_revenue"])
            .sort(["sum_revenue", "n_name"], ascending=[False, True]))
    return plan.execute()


def structure(state: dict, chips: int, counters: dict) -> dict:
    return {}
