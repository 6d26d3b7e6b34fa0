"""Device time in index gathers, mean over the chips, per traced query;
0 where the trace holds none.

What counts: the operations of ``run.trace["ops_s"]`` whose short name
(``bench/trace_reduce.py::short_name``) is

    <module>/%fusion fusion:kCustom <type>     or
    <module>/%fusion.<n> fusion:kCustom <type>

in any jitted program: ``jit_join_gather`` (``join.expand``,
``join.gather_left``, ``join.gather_right``), ``jit_hash_groupby`` and the
four-chip cell's ``jit_partial_fn`` / ``jit_final_fn`` (``groupby.reduce``'s
takes through the segment ends), ``jit_gather_fn``, ``jit_rfn`` (the
exchange's plane gather), ``jit_plan_*`` and ``jit__take`` (a column
through a filter's or a compaction's index).  XLA:TPU gives a ``gather``
a custom fusion of its own and leaves it the bare name ``%fusion``, where
it names a loop or an output fusion after what it holds
(``%broadcast_clamp_fusion``, ``%iota_or_fusion``); the Pallas kernels are
``custom-call:tpu_custom_call`` and the sorts ``sort``, so neither is
here.  Every ``kind=kCustom`` fusion of the compiled join + group-by step
calls a computation that holds exactly one ``gather``
(``tests/test_tpu_compile.py::test_entry_step_compiles`` holds that for
the described v5e).  Should a bare custom fusion that is no gather turn up
(a scatter, a dynamic-update-slice), the compiled text tells them apart --
``compiled.as_text()``: the computation after ``calls=`` has no
``gather(`` -- and its ``<module>/%fusion.<n>`` is then to be taken out
here by name.

Self time, as the reduction gives every instant to the innermost
operation.  The price of one 2^24-slot 32-bit lane is this number over the
lanes a query sends through an index (15 in the join cells: ten of the
join's output, two of its expansion, three of the group-by's reductions).
"""
import re

_GATHER = re.compile(r"^[^/ ]+/%fusion(\.\d+)? fusion:kCustom ")


def read(run):
    t = run.trace
    if not t:
        return None
    secs = sum(s for name, s in t["ops_s"].items() if _GATHER.match(name))
    return secs / t["queries"] * 1e3
