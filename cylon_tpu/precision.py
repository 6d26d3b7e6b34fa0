"""Accumulation-precision policy: wide (64-bit) vs narrow (32-bit) kernels.

The reference accumulates in the KernelTraits state type — double for
MEAN/VAR, the input type for SUM/MIN/MAX (compute/aggregate_kernels.hpp:
38-200).  On TPU, 64-bit tensors are a liability: f64 is software-emulated,
64-bit scatters profile ~8x slower than 32-bit ones, and some fused 64-bit
prefix programs have crashed this XLA TPU backend outright (see
ops/groupby.py notes).  So every kernel that needs a float accumulator or
derives float statistics consults this policy:

- ``wide``   — f64 accumulation/derivation, int64 counts.  The default on
  CPU meshes; bit-compatible with the reference goldens.
- ``narrow`` — f32 accumulation/derivation, int32 count scatters (widened
  to int64 only at column boundaries).  The default on TPU.  Integer SUM
  still accumulates int64 (a 100M-row int32 sum overflows i32); that is
  correctness-mandated, exactly like the reference's int64 sum state.

Resolution order: explicit ``set_accumulation()`` > ``CYLON_TPU_ACCUM``
env var > backend default (tpu -> narrow, else wide).  The mode is read at
trace time, so switch it before the first jitted compute of the process;
``set_accumulation`` clears jit caches to force retraces when switched
mid-process.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import config

_MODE: str | None = None  # None = auto-resolve


def set_accumulation(mode: str | None) -> None:
    """Force ``"wide"`` or ``"narrow"`` accumulation (None = auto)."""
    global _MODE
    if mode not in (None, "wide", "narrow"):
        raise ValueError(f"accumulation mode must be wide/narrow, got {mode}")
    if mode != _MODE:
        jax.clear_caches()  # jitted kernels read the mode at trace time
    _MODE = mode


def on_tpu() -> bool:
    """True when the default backend is a TPU — the one platform test every
    backend-aware "auto" default in the package goes through."""
    return jax.default_backend() == "tpu"


def accumulation_mode() -> str:
    if _MODE is not None:
        return _MODE
    env = config.knob("CYLON_TPU_ACCUM")
    if env in ("wide", "narrow"):
        return env
    return "narrow" if on_tpu() else "wide"


def narrow() -> bool:
    return accumulation_mode() == "narrow"


def float_acc():
    """Accumulator dtype for float prefix sums / derived statistics."""
    return jnp.float32 if narrow() else jnp.float64


def float_acc_for(data_dtype):
    """Float accumulator for a float SUM: input-width in wide mode (an f32
    sum stays f32, like the reference's input-typed sum state), f32 in
    narrow mode (f64 data trades precision for a native-width scatter)."""
    if narrow():
        return jnp.float32
    return jnp.float64 if data_dtype == jnp.float64 else jnp.float32


def int_acc():
    """Accumulator for integer sums — always wide; overflow is worse than
    an emulated 64-bit scatter."""
    return jnp.int64


def count_acc():
    """Count scatters always run i32 (cardinality < 2^31 per shard)."""
    return jnp.int32
