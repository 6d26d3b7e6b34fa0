"""Worker for the fleet-router smoke (NOT a pytest module).

One serving replica of the router smoke in tools/full_tree_cold.sh: an
elastic `Agent` joining the `QueryRouter`'s control plane, a
`QueryService` + `ReplicaServer` pair on an ephemeral data-plane port,
and a registered ``kjoin`` op — chunked join behind the seeded fault
site ``router.pass.r<rank>`` so the driver can ``rank_kill`` one
replica mid-flood (``CYLON_TPU_FAULT_PLAN=router.pass.r1@N=rank_kill``
-> ``os._exit(137)`` exactly at its Nth dispatched flood request).

Traces export INCREMENTALLY (tmp + atomic rename every 0.2s): the
killed replica's completed-request spans survive its own death, which
is what lets the merged timeline show one trace spanning router + both
replicas even though ``os._exit`` flushes nothing.

Exit codes: 0 clean stand-down (coordinator gone = smoke over),
137 injected kill.

Usage: python -m tests.router_worker <rank> <world> <host:port>
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cylon_tpu import elastic, resilience  # noqa: E402
from cylon_tpu.exec import chunked_join  # noqa: E402
from cylon_tpu.obs import export  # noqa: E402
from cylon_tpu.router import ReplicaServer  # noqa: E402
from cylon_tpu.serve import QueryService  # noqa: E402


def _export_snapshot(rank: int) -> None:
    """Atomic incremental trace + metrics export: a rank_kill mid-write
    must never leave a torn file for trace_merge to choke on, and the
    self-healing journal smoke asserts this replica's durable.* counters
    from the metrics artifact after the fleet stands down."""
    for prefix, exporter in (("trace", export.export_trace),
                             ("metrics", export.export_metrics)):
        final = export._artifact_path(None, prefix, rank)
        tmp = final + f".tmp.{os.getpid()}"
        try:
            exporter(path=tmp, rank=rank)
            os.replace(tmp, final)
        except OSError:
            pass  # exports are best-effort; the next tick retries


def main() -> int:
    rank = int(sys.argv[1])
    world = int(sys.argv[2])
    address = sys.argv[3]

    agent = elastic.Agent(address, rank).start()
    svc = QueryService(name=f"replica{rank}")

    def kjoin(left, right, *, ctx=None, pass_guard=None, **kw):
        # the seeded kill site: rank_kill here is a replica dying at a
        # request dispatch boundary, with its queue full of re-routable
        # work
        resilience.fault_point(f"router.pass.r{rank}")
        return chunked_join(left, right, ctx=ctx, pass_guard=pass_guard,
                            **kw)

    # idempotent=True: kjoin is a pure journaled join, so the chaos
    # smoke's hedges are allowed to speculate it onto a second replica
    svc.register_op("kjoin", kjoin, idempotent=True)
    rep = ReplicaServer(svc)
    rep.attach(agent)
    print(f"router_worker r{rank}: serving at "
          f"{rep.address[0]}:{rep.address[1]} (world {world})",
          flush=True)
    try:
        while not (agent.coordinator_down or agent.fenced):
            time.sleep(0.2)
            _export_snapshot(rank)
    finally:
        _export_snapshot(rank)
        rep.close()
        svc.close(timeout=10.0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
