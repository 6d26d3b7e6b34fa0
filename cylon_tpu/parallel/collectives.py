"""Thin wrappers over XLA collectives.

Replaces the reference's typed MPI collective wrappers
(cpp/src/cylon/net/mpi/mpi_operations.cpp:18-78 mpi::AllReduce /
GetMPIOp / GetMPIDataType and net/comm_operations.hpp ReduceOp): inside a
``shard_map`` region psum/pmin/pmax over the mesh axis ARE the AllReduce;
there is no type dispatch because XLA handles element types natively.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..context import PARTITION_AXIS


def allreduce_sum(x):
    return jax.lax.psum(x, PARTITION_AXIS)


def _sums_only(x) -> bool:
    """XLA:TPU lowers an all-reduce of a 64-bit type only for sums: its
    extremes are gathered and reduced on every shard instead."""
    return jnp.dtype(x.dtype).itemsize == 8


def allreduce_min(x):
    if _sums_only(x):
        return jnp.min(allgather(x), axis=0)
    return jax.lax.pmin(x, PARTITION_AXIS)


def allreduce_max(x):
    if _sums_only(x):
        return jnp.max(allgather(x), axis=0)
    return jax.lax.pmax(x, PARTITION_AXIS)


def allgather(x, axis: int = 0):
    return jax.lax.all_gather(x, PARTITION_AXIS, axis=axis)


def all_to_all(x, split_axis: int = 0, concat_axis: int = 0):
    return jax.lax.all_to_all(x, PARTITION_AXIS, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ragged_all_to_all(operand, output, input_offsets, send_sizes,
                      output_offsets, recv_sizes):
    """``lax.ragged_all_to_all`` over the partition axis (exact-traffic
    exchange; not implemented by every backend — callers probe via
    parallel.ops._ragged_enabled).  Centralized so the packed-plane and
    per-buffer shuffle bodies share one launch site."""
    return jax.lax.ragged_all_to_all(
        operand, output, input_offsets, send_sizes, output_offsets,
        recv_sizes, axis_name=PARTITION_AXIS)


def my_rank():
    return jax.lax.axis_index(PARTITION_AXIS)
