"""Execution context.

TPU-native analog of the reference's ``CylonContext`` (reference:
cpp/src/cylon/ctx/cylon_context.hpp:29-146, cylon_context.cpp:25-116) and its
communicator configs (cpp/src/cylon/net/comm_config.hpp, comm_type.hpp:20-22).

Where the reference initializes MPI and hands out per-operation "edge"
sequence numbers so concurrent all-to-alls don't collide, the TPU context
owns a ``jax.sharding.Mesh`` over the device axis ``'p'`` — the analog of
``MPI_COMM_WORLD`` — and nothing else: XLA orders collectives by program
order, so edge tags are unnecessary (kept only for API parity).

``world_size`` == number of devices on the mesh; a "rank" is a mesh position.
Multi-host pods extend the same mesh across processes via
``jax.distributed.initialize`` (collectives then ride ICI within a slice and
DCN across slices — the role MPI point-to-point plays in the reference).
"""
from __future__ import annotations

import enum
import os
import threading
from typing import Dict, List, Optional

import numpy as np

PARTITION_AXIS = "p"


class CommType(enum.IntEnum):
    """Communication backends (reference: net/comm_type.hpp:20-22 enumerates
    LOCAL/MPI/TCP/UCX with only MPI implemented; here the distributed backend
    is XLA collectives over ICI/DCN)."""

    LOCAL = 0
    TPU = 1       # XLA collectives over ICI/DCN (the MPI replacement)
    CPU_SIM = 2   # host-simulated multi-device mesh (tests)


class CommConfig:
    """Base communicator config (reference: net/comm_config.hpp)."""

    def comm_type(self) -> CommType:
        raise NotImplementedError


class LocalConfig(CommConfig):
    def comm_type(self) -> CommType:
        return CommType.LOCAL


class TPUConfig(CommConfig):
    """Distributed config over a device mesh (reference analog: MPIConfig,
    net/mpi/mpi_communicator.cpp:27-49).

    devices: explicit device list; default = all of ``jax.devices()``.

    Multi-host (the reference's multi-node MPI world,
    net/mpi/mpi_communicator.cpp:51-60 MPI_Init + COMM_WORLD): pass
    ``coordinator_address`` + ``num_processes`` + ``process_id`` and every
    process joins one global mesh via ``jax.distributed.initialize`` —
    collectives then ride ICI within a slice and DCN across hosts.
    """

    def __init__(self, devices=None, world_size: Optional[int] = None,
                 coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 local_device_ids=None):
        self.devices = devices
        self.world_size = world_size
        self.coordinator_address = coordinator_address
        self.num_processes = num_processes
        self.process_id = process_id
        self.local_device_ids = local_device_ids

    def comm_type(self) -> CommType:
        return CommType.TPU


class ElasticConfig(TPUConfig):
    """Config for one member of an ELASTIC gang (PR 6): each process
    drives its own local mesh while a TCP control plane
    (``cylon_tpu.elastic``: coordinator + per-process agent, heartbeats,
    epoch-numbered membership) tracks who is alive.  On a membership
    change the gang re-forms at the shrunken world — re-init rather than
    reshape, because XLA cannot reshape a live mesh — and the durable
    journal carries completed work across the shrink.

    ``coordinator``: ``host:port`` of the running `elastic.Coordinator`
    (default: the ``CYLON_TPU_ELASTIC_COORD`` knob); ``rank``: this
    process's gang rank.  ``devices``/``world_size`` configure the LOCAL
    mesh exactly as on `TPUConfig`.
    """

    def __init__(self, rank: int, coordinator: Optional[str] = None,
                 devices=None, world_size: Optional[int] = None):
        super().__init__(devices=devices, world_size=world_size)
        self.rank = int(rank)
        self.coordinator = coordinator


class CylonContext:
    """Entry point holding the mesh, config map and sequence counter.

    Mirrors the reference surface: ``Init/InitDistributed/GetRank/
    GetWorldSize/GetNeighbours/AddConfig/GetConfig/GetNextSequence/Barrier/
    Finalize`` (ctx/cylon_context.hpp:29-146), re-based on a JAX mesh.
    """

    def __init__(self, config: Optional[CommConfig] = None, distributed: bool = False):
        import jax

        self._config: Dict[str, str] = {}
        self._sequence = 0
        self._lock = threading.Lock()
        self._finalized = False
        self.distributed = distributed or (
            config is not None and config.comm_type() != CommType.LOCAL)
        if not self.distributed:
            self.devices = np.array(jax.devices()[:1])
        else:
            cfg = config if isinstance(config, TPUConfig) else TPUConfig()
            if cfg.num_processes is not None and cfg.num_processes > 1:
                # the MPI_Init moment: join the global runtime before any
                # backend initializes, so jax.devices() spans every host
                if not jax.distributed.is_initialized():
                    jax.distributed.initialize(
                        coordinator_address=cfg.coordinator_address,
                        num_processes=cfg.num_processes,
                        process_id=cfg.process_id,
                        local_device_ids=cfg.local_device_ids)
            devs = list(cfg.devices) if cfg.devices is not None else list(jax.devices())
            if cfg.world_size is not None:
                devs = devs[: cfg.world_size]
            self.devices = np.array(devs)
        from jax.sharding import Mesh

        self.mesh = Mesh(self.devices, (PARTITION_AXIS,))
        self._elastic_agent = None
        if isinstance(config, ElasticConfig):
            # join the gang AFTER the local mesh exists: membership is a
            # control-plane fact layered over per-process meshes (the
            # gang re-forms, the mesh never reshapes)
            from . import elastic

            self._elastic_agent = elastic.connect(config.rank,
                                                  config.coordinator)
        elif self.distributed and isinstance(config, TPUConfig):
            # env-driven opt-in (CYLON_TPU_ELASTIC=1 + _ELASTIC_COORD):
            # a plain distributed context joins the gang without code
            # changes — the deployment path where each host only gets
            # environment variables.  The gang rank is the process id
            # (single-process-per-host contexts default to rank 0).
            from . import elastic

            if elastic.elastic_enabled():
                rank = (config.process_id
                        if config.process_id is not None else 0)
                self._elastic_agent = elastic.connect(rank)
        # OpenMetrics scrape listener (CYLON_TPU_METRICS_PORT): knob-
        # driven, once per process, no-op at 0; a failed bind warns
        # inside ensure_server and must never fail context bring-up
        from .obs import openmetrics

        openmetrics.ensure_server()

    # -- reference-parity static factories (ctx/cylon_context.cpp:25-43) ----
    @staticmethod
    def Init() -> "CylonContext":
        return CylonContext(LocalConfig(), distributed=False)

    @staticmethod
    def InitDistributed(config: CommConfig) -> "CylonContext":
        if config.comm_type() == CommType.LOCAL:
            raise ValueError("Local communication config passed to InitDistributed")
        return CylonContext(config, distributed=True)

    # -- identity ----------------------------------------------------------
    def GetRank(self) -> int:
        # process-level rank (multi-host); mesh positions are the data ranks
        if self._elastic_agent is not None:
            return self._elastic_agent.rank
        import jax

        return jax.process_index() if self.distributed else 0

    def elastic_agent(self):
        """The `elastic.Agent` this context joined the gang with, or
        None for fixed-world contexts."""
        return self._elastic_agent

    def GetWorldSize(self) -> int:
        return int(self.devices.size) if self.distributed else 1

    @property
    def world_size(self) -> int:
        return self.GetWorldSize()

    def GetNeighbours(self, include_self: bool = False) -> List[int]:
        # elastic contexts: neighbours are the LIVE gang members (the
        # mesh world size is per-process and says nothing about peers)
        if self._elastic_agent is not None:
            return [m for m in self._elastic_agent.members
                    if include_self or m != self._elastic_agent.rank]
        return [i for i in range(self.GetWorldSize())
                if include_self or i != self.GetRank()]

    def is_distributed(self) -> bool:
        return self.distributed

    # -- config k/v map (cylon_context.cpp:60-69) --------------------------
    def AddConfig(self, key: str, value: str) -> None:
        self._config[key] = value

    def GetConfig(self, key: str, default: str = "") -> str:
        return self._config.get(key, default)

    # -- resilience --------------------------------------------------------
    def retry_policy(self):
        """Transient-failure retry policy for operations on this context.
        Unset contexts re-read the env knobs (CYLON_TPU_RETRY_*) on every
        call so tests and long-lived processes see live values; an
        explicit `set_retry_policy` pins one."""
        policy = getattr(self, "_retry_policy", None)
        if policy is not None:
            return policy
        from .resilience import RetryPolicy

        return RetryPolicy.from_env()

    def set_retry_policy(self, policy) -> None:
        self._retry_policy = policy

    def collective_retry_policy(self):
        """Policy for retrying a whole SPMD collective (shuffle exchange,
        distributed per-pass join).  Safe only when ONE process drives
        every mesh device: re-entering the collective from a single host
        of a multi-process mesh would issue a program the peers — blocked
        inside or already past the original — never join, desyncing the
        mesh.  Multi-process runs therefore get a no-retry policy and the
        failure surfaces immediately."""
        from .resilience import RetryPolicy

        import jax

        if self.distributed and jax.process_count() > 1:
            base = self.retry_policy()
            return RetryPolicy(max_retries=0, base_s=base.base_s,
                               max_s=base.max_s)
        return self.retry_policy()

    # -- sequence / barrier / finalize -------------------------------------
    def GetNextSequence(self) -> int:
        # XLA orders collectives by program order; kept for API parity only
        with self._lock:
            self._sequence += 1
            return self._sequence

    def Barrier(self) -> None:
        """Block the host until all devices reach this point — a 1-element
        psum over the mesh, the collective analog of MPI_Barrier.  The jitted
        program and its input are cached on the context so repeat barriers
        cost microseconds, not a recompile."""
        if not self.distributed:
            return
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        cached = getattr(self, "_barrier_fn", None)
        if cached is None:
            from .utils import shard_map

            mesh = self.mesh
            fn = jax.jit(shard_map(
                lambda v: jax.lax.psum(v, PARTITION_AXIS),
                mesh=mesh, in_specs=P(PARTITION_AXIS), out_specs=P()))
            x = jax.device_put(
                jnp.zeros((self.GetWorldSize(),), jnp.int32),
                NamedSharding(mesh, P(PARTITION_AXIS)))
            cached = (fn, x)
            self._barrier_fn = cached
        fn, x = cached
        fn(x).block_until_ready()

    def Finalize(self) -> None:
        self._finalized = True
        if self._elastic_agent is not None:
            self._elastic_agent.leave()

    def __repr__(self) -> str:
        kind = "distributed" if self.distributed else "local"
        return f"CylonContext({kind}, world_size={self.GetWorldSize()})"


class LRUCache(dict):
    """dict with a size bound: setting past ``maxsize`` evicts the least
    recently used entry (``get`` hits refresh recency).  Bounds program
    caches keyed by caller-supplied objects (e.g. select predicates) so a
    long-lived context issuing ad-hoc lambdas cannot grow without limit."""

    def __init__(self, maxsize: int):
        super().__init__()
        self.maxsize = maxsize

    def get(self, key, default=None):
        if key in self:
            val = super().pop(key)
            super().__setitem__(key, val)
            return val
        return default

    def __getitem__(self, key):
        # route through get() so bracket reads refresh recency too — a
        # plain-dict __getitem__ would silently degrade the LRU to FIFO
        sentinel = object()
        val = self.get(key, sentinel)
        if val is sentinel:
            raise KeyError(key)
        return val

    def __setitem__(self, key, value):
        if key in self:
            super().pop(key)
        super().__setitem__(key, value)
        while len(self) > self.maxsize:
            super().pop(next(iter(self)))

    def setdefault(self, key, default=None):
        sentinel = object()
        val = self.get(key, sentinel)
        if val is sentinel:
            self[key] = default
            return default
        return val

    def update(self, *args, **kwargs):
        # honor the size bound and recency on bulk writes as well
        for k, v in dict(*args, **kwargs).items():
            self[k] = v


def ctx_cache(ctx: CylonContext, name: str, maxsize: int | None = None) -> Dict:
    """Per-context cache dict stored on the context object itself — dies
    with the context (no id()-reuse aliasing, no global leak).  Used for
    jitted shard programs and plan capacities keyed by this context.
    ``maxsize`` (honored at creation) makes it an LRU."""
    cache = getattr(ctx, name, None)
    if cache is None:
        cache = {} if maxsize is None else LRUCache(maxsize)
        setattr(ctx, name, cache)
    return cache


_default_local: Optional[CylonContext] = None


def default_context() -> CylonContext:
    global _default_local
    if _default_local is None:
        _default_local = CylonContext.Init()
    return _default_local
