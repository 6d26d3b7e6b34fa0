"""cylint CLI: ``python -m cylon_tpu.analysis [paths...]``.

Exit codes: 0 — clean; 1 — findings; 2 — usage/internal error.

The jaxpr budget gate (``--budgets`` / ``--write-budgets``) needs a
virtual multi-device CPU platform; when jax has not been imported yet
this module sets the same platform environment the test harness uses, so
``tools/cylint cylon_tpu --budgets`` works from a bare shell.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _prepare_cpu_mesh() -> None:
    """Platform env for budget tracing — tests/conftest.py's virtual-mesh
    harness, inlined.  These are platform controls, not ``CYLON_TPU_*``
    knobs; both are read when the backend initializes."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get(  # cylint: disable=CY102 -- platform harness setup (JAX_PLATFORMS/XLA_FLAGS), not a CYLON_TPU_* knob read
        "XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="cylint",
        description="repo-native static analysis: trace-safety (AST) and "
                    "collective budgets (jaxpr)")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to scan (default: the "
                         "cylon_tpu package)")
    ap.add_argument("--budgets", action="store_true",
                    help="also trace the entry points and enforce the "
                         "committed collective budgets")
    ap.add_argument("--write-budgets", action="store_true",
                    help="regenerate cylon_tpu/analysis/budgets/*.json "
                         "from a live trace (commit the result)")
    ap.add_argument("--lockgraph", action="store_true",
                    help="also run the elastic/serve smoke under the "
                         "runtime lock recorder and check the observed "
                         "lock-order edges against the committed golden "
                         "and the static lock graph")
    ap.add_argument("--write-lockgraph", action="store_true",
                    help="regenerate cylon_tpu/analysis/lockgraph/"
                         "lock_order.json from a recorded smoke run "
                         "(commit the result)")
    ap.add_argument("--knobs", action="store_true",
                    help="print the authoritative CYLON_TPU_* knob table "
                         "and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON")
    args = ap.parse_args(argv)

    from .. import config
    from .astlint import RULES, scan_paths

    if args.knobs:
        print(config.knob_table())
        return 0
    if args.list_rules:
        for rule, desc in RULES.items():
            print(f"{rule}  {desc}")
        return 0

    if args.budgets or args.write_budgets:
        _prepare_cpu_mesh()

    findings = []
    paths = args.paths
    if not paths and not (args.write_budgets or args.write_lockgraph):
        paths = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    if paths:
        findings.extend(scan_paths(paths))

    if args.write_budgets:
        from .budgets import write_budgets

        for p in write_budgets():
            print(f"wrote {p}", file=sys.stderr)
    elif args.budgets:
        from .budgets import check_budgets

        findings.extend(check_budgets())

    if args.write_lockgraph or args.lockgraph:
        from .locks import (check_lockgraph, smoke_observed, static_edges,
                            write_lockgraph)

        static = static_edges()
        observed = smoke_observed()
        if args.write_lockgraph:
            print(f"wrote {write_lockgraph(observed, static)}",
                  file=sys.stderr)
        else:
            findings.extend(check_lockgraph(observed, static))

    if args.json:
        print(json.dumps([f.__dict__ for f in findings], indent=2))
    else:
        for f in findings:
            print(f.render())
        if findings:
            print(f"\ncylint: {len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
