"""Utility subsystem (reference: cpp/src/cylon/util/ — uuid v4 uuid.cpp,
value printing to_string.hpp, sort/sample helpers arrow_utils.cpp — and
python/pycylon/util/benchutils.py)."""
from __future__ import annotations

import uuid as _uuid

from .benchutils import (benchmark_with_repetitions,  # noqa: F401
                         benchmark_with_repitions, time_conversion)
from .timing import enable as enable_timing  # noqa: F401
from .timing import report as timing_report  # noqa: F401
from .timing import reset as timing_reset  # noqa: F401
from .timing import span  # noqa: F401


def generate_uuid_v4() -> str:
    """reference: util/uuid.cpp generate_uuid_v4."""
    return str(_uuid.uuid4())


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` — every shard_map construction in the tree goes
    through here (cylint resolves the builders by this name)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pow2ceil(n: int, min_size: int = 8) -> int:
    """Smallest power of two >= n (>=1), floored at ``min_size`` — the one
    capacity-rounding rule shared by every planner and kernel so shard
    capacities never disagree."""
    return max(min_size, 1 << (max(1, int(n)) - 1).bit_length())


def to_string(value, quote_strings: bool = False) -> str:
    """CSV-ish scalar rendering used by Table.print (reference:
    util/to_string.hpp): nulls print empty, strings optionally quoted."""
    if value is None:
        return ""
    if isinstance(value, float):
        return repr(value)
    if isinstance(value, bool):
        return "true" if value else "false"
    if isinstance(value, (bytes, bytearray)):
        value = value.decode("utf-8", "replace")
    if isinstance(value, str) and quote_strings:
        return f'"{value}"'
    return str(value)
