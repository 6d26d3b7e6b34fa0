#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is found by its name in BENCHMARK.json:

    bench/configs/<config>.json       sizes, guarantees, limits, "driver"
                                      and, where it differs, "reference"
    bench/drivers/<driver>.py         the system under test
    bench/references/<reference>.py   data from the seed, plain reference,
                                      lower-precision control, comparison
    bench/traffic/<traffic>.json      parameters of the traffic generator
    bench/metrics/<metric>.py         read(run) -> number or None

The last line of standard output is the result.  A platform other than
"tpu", or fewer devices than the cell's ``chips``, ends the run with code
2 and no result: there is no CPU mode.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import traffic as traffic_mod  # noqa: E402
from bench import trace_reduce  # noqa: E402

# a traced run traces queries until this many seconds of the window have
# passed: traces of longer windows are large, and reading one is part of
# the run's 360 seconds
TRACE_SECONDS = 20.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# set-up runs the cell's cycle at least twice, and then until a pass
# compiles nothing
WARM_PASSES_MIN, WARM_PASSES_MAX = 2, 6


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def load_json(*path) -> dict:
    with open(os.path.join(*path)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def load_cell(workload: str) -> SimpleNamespace:
    """The cell's entries of the benchmark and the files they name."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"there are {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, entry["file"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]), cfg=cfg,
        mix=traffic_mod.load(cell["traffic"]),
        driver=importlib.import_module("bench.drivers." + cfg["driver"]),
        reference=importlib.import_module(
            "bench.references." + cfg.get("reference", cfg["driver"])),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def load_reader(metric_name: str):
    path = os.path.join(BENCH, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def check_devices(chips: int):
    """The chips this cell runs on, or exit 2: no fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"platform is {devs[0].platform!r}, not 'tpu'")
        sys.exit(2)
    if len(devs) < chips:
        log(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
        sys.exit(2)
    return devs[:chips]


class CompileCounter:
    """Counts the programs JAX compiled or loaded from its cache."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def program_counters() -> dict:
    from cylon_tpu.obs import metrics

    return dict(metrics.snapshot()["counters"])


def program_spans() -> dict:
    from cylon_tpu.obs import spans

    return dict(spans.aggregate_report())


def delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k)
        if isinstance(v, tuple):
            b = b or (0.0, 0)
            out[k] = (v[0] - b[0], v[1] - b[1])
        else:
            out[k] = v - (b or 0)
    return out


def warm_up(cell, state, cycle, compiles) -> int:
    """Run the cycle until a whole pass of it compiled nothing: the join's
    capacity cache settles over the first passes, and each new capacity is
    a new program.  Returns the passes made."""
    import jax

    for n in range(1, WARM_PASSES_MAX + 1):
        before = compiles.count
        for query in cycle:
            table = cell.driver.run(state, query)
            jax.block_until_ready(table.columns)
            cell.driver.fetch(table)
        log(f"warm-up pass {n}: {compiles.count - before} program(s) "
            f"compiled or loaded")
        if n >= WARM_PASSES_MIN and compiles.count == before:
            return n
    raise RuntimeError(
        f"still compiling after {WARM_PASSES_MAX} warm-up passes")


def min_median_max(values: list) -> str:
    if not values:
        return "none"
    return " / ".join(f"{v:.1f}" for v in (
        min(values), traffic_mod.percentile(values, 0.5), max(values)))


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if any(p is None for p in peaks):
        raise RuntimeError("a device reports no peak_bytes_in_use")
    return int(max(peaks))


def run_window(cell, state, data, cycle, seconds: float, trace_dir) -> tuple:
    """Drive the traffic for ``seconds``.  Returns (t0, records, answers):
    one record and one fetched answer for every query issued."""
    import jax
    from jax.profiler import TraceAnnotation

    answers = []
    tracing = {"on": False, "t0": None}

    def stop_trace():
        if tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"] = False

    def issue(i: int, query: dict) -> dict:
        if (tracing["on"] and
                time.perf_counter() - tracing["t0"] >= TRACE_SECONDS):
            stop_trace()
        rec = {"i": i, "rows": cell.reference.input_rows(data, query),
               "ok": False, "start": time.perf_counter()}
        try:
            with TraceAnnotation("bench.query"):
                table = cell.driver.run(state, query)
                jax.block_until_ready(table.columns)
                rec["ran"] = time.perf_counter()
                with TraceAnnotation("bench.fetch"):
                    answers.append(cell.driver.fetch(table))
            rec["ok"] = True
        except Exception:
            log(f"query {i} failed:\n{traceback.format_exc()}")
            rec.setdefault("ran", time.perf_counter())
            answers.append(None)
        rec["end"] = time.perf_counter()
        return rec

    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing.update(on=True, t0=time.perf_counter())
    try:
        t0, records = traffic_mod.drive(cell.mix, issue, cycle, seconds)
    finally:
        stop_trace()
    return t0, records, answers


def decide_correct(cell, data, cycle, records, answers, structure) -> tuple:
    """({name: [value, limit]}, the reference's answers by query) over
    every answer of the window: each is compared with the plain
    reference's answer to its query, and the widest reading of each number
    is kept."""
    limits = cell.cfg["limits"]
    expected = {}
    worst = {}
    for rec, got in zip(records, answers):
        if got is None:
            continue
        qi = rec["i"] % len(cycle)
        if qi not in expected:
            expected[qi] = cell.reference.answer(data, cycle[qi])
        for name, value in cell.reference.compare(got, expected[qi]).items():
            worst[name] = max(worst.get(name, 0), value)
    worst.update(structure)
    missing = [n for n in worst if n not in limits]
    if missing:
        raise RuntimeError(f"no limit in the configuration for {missing}")
    return ({n: [v, limits[n]] for n, v in worst.items()}, expected)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices, t_process: float) -> dict:
    """Everything past the look for a chip: set-up, the window, the
    memory reading, the reference and the comparison.  Returns the
    result line as a dict."""
    import jax

    from cylon_tpu import CylonContext, TPUConfig
    from cylon_tpu.utils.compile_cache import enable_persistent_compile_cache

    cell = load_cell(workload)
    cache_dir = enable_persistent_compile_cache(min_compile_secs=0)
    compiles = CompileCounter()
    log(f"cell {cell.name}: config driver {cell.cfg['driver']}, "
        f"{cell.chips} chip(s), compile cache {cache_dir}")

    # ---- set-up: data from the seed, tables to the chips, warm-up ----
    ctx = CylonContext.InitDistributed(TPUConfig(world_size=cell.chips))
    data = cell.reference.make_data(cell.cfg, cell.chips, seed)
    cycle = cell.reference.queries(cell.cfg, seed)
    state = cell.driver.build(ctx, cell.cfg, data)
    log("modes", json.dumps(cell.driver.modes()))
    passes = warm_up(cell, state, cycle, compiles)
    setup_compiles = compiles.count
    counters0, spans0 = program_counters(), program_spans()

    # ---- the window ----
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_dir:
        setup_s = time.perf_counter() - t_process
        t0, records, answers = run_window(
            cell, state, data, cycle, seconds, trace_dir if trace else None)
        window_compiles = compiles.count - setup_compiles
        counters = delta(program_counters(), counters0)
        counters["queries"] = sum(1 for r in records if r["ok"])
        spans = delta(program_spans(), spans0)
        peak = memory_peak_bytes(devices)
        reduced = {}
        if trace:
            reduced = trace_reduce.reduce(trace_reduce.load_events(
                trace_reduce.find_xplane(trace_dir)))
            if not reduced:
                raise RuntimeError("the trace holds no device operation "
                                   "or no bench.query annotation")
    structure = cell.driver.structure(state, cell.chips, counters)
    log(f"window: {len(records)} queries in "
        f"{max(r['end'] for r in records) - t0:.3f} s after {setup_s:.1f} s "
        f"of set-up; {traffic_mod.rows_per_s(t0, records):.0f} rows/s; "
        f"{window_compiles} program(s) compiled in it")
    log("query ms, min / median / max:",
        min_median_max(traffic_mod.latencies_ms(records)), "; of it fetch:",
        min_median_max([(r["end"] - r["ran"]) * 1e3
                        for r in records if r["ok"]]))
    log("program spans in the window",
        json.dumps({k: [round(v[0], 6), v[1]] for k, v in spans.items()}))
    log("program counters in the window", json.dumps(counters))

    # ---- the program's state goes, then the reference runs ----
    state.clear()
    del state
    t_ref = time.perf_counter()
    compared, expected = decide_correct(cell, data, cycle, records, answers,
                                        structure)
    log(f"reference and comparison took "
        f"{time.perf_counter() - t_ref:.1f} s over {len(answers)} answers")
    failed = sum(1 for r in records if not r["ok"])
    correct = failed == 0 and bool(records) and all(
        value <= limit for value, limit in compared.values())

    run = SimpleNamespace(
        cell=cell, t0=t0, records=records, setup_s=setup_s, trace=reduced,
        counters=counters, spans=spans, compiles_in_window=window_compiles,
        memory_peak_bytes=peak, warm_passes=passes,
        peaks=peaks_for(devices[0].device_kind),
        work_bytes=[cell.reference.work_bytes(data, cycle[q], exp)
                    for q, exp in sorted(expected.items())])
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["compared"] = compared
    return result


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise RuntimeError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def report(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, and the result as the last line of standard output."""
    for name, (value, limit) in result["compared"].items():
        log(f"compared {name} = {value!r} limit {limit!r} "
            f"{'ok' if value <= limit else 'FAILS'}")
    log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = check_devices(cell.chips)
    report(run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    devices, _T_PROCESS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
