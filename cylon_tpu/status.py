"""Status/error-code system.

TPU-native analog of the reference's ``cylon::Status`` / ``cylon::Code``
(reference: cpp/src/cylon/status.hpp, cpp/src/cylon/code.cpp).  The reference
models its codes after Arrow's; we keep the same code set so messages and
call-sites translate 1:1, but expose them Python-first (exceptions are the
idiomatic failure path in a JAX framework; ``Status`` objects remain available
for API parity with pycylon).
"""
from __future__ import annotations

import enum
from dataclasses import dataclass


class Code(enum.IntEnum):
    """Error codes (reference: cpp/src/cylon/code.cpp)."""

    OK = 0
    OutOfMemory = 1
    KeyError = 2
    TypeError = 3
    Invalid = 4
    IOError = 5
    CapacityError = 6
    IndexError = 7
    UnknownError = 9
    NotImplemented = 10
    SerializationError = 11
    RError = 13
    CodeGenError = 40
    ExpressionValidationError = 41
    ExecutionError = 42
    AlreadyExists = 45
    Timeout = 46
    # elastic-membership codes (PR 6; like Timeout, extensions past the
    # reference's table).  Neither is retryable: a lost coordinator has
    # no one to retry against, and re-running a pass into a changed
    # membership is the desync PR 1's no-retry-collectives rule bans —
    # the elastic loop re-PLANS at the new world instead.
    Unavailable = 47      # control plane gone / service draining or closed
    EpochMismatch = 48    # membership moved under in-flight work
    # serving codes (PR 7).  ResourceExhausted is the ADMISSION-layer
    # sibling of OutOfMemory: the request was never attempted because a
    # bounded queue / tenant budget had no room — deterministically
    # retryable by the CALLER (rejects carry a retry-after hint), but
    # never by the engine (nothing in-flight exists to retry).
    # Cancelled is a caller's own decision echoed back; retrying it
    # would countermand the cancel, so it is non-retryable too.
    ResourceExhausted = 49
    Cancelled = 50


# Failure-text classification tables (lowercase substrings).  PJRT raises
# one exception type (XlaRuntimeError) whose message carries the absl
# status code, so classification is textual by necessity; the patterns
# cover the RESOURCE_EXHAUSTED / allocator shapes TPU OOMs actually emit
# and the deadline/comm shapes a flaky link emits.  resilience.py's
# injected faults reuse these exact message shapes.
_OOM_PATTERNS = (
    "resource_exhausted", "resource exhausted", "out of memory",
    "failed to allocate", "allocation failure", "exceeds hbm",
    "hbm capacity", "exceeds the memory",
)
_TRANSIENT_PATTERNS = (
    "deadline_exceeded", "deadline exceeded", "timed out", "timeout",
    "unavailable", "connection reset", "connection refused",
    "connection closed", "socket closed", "broken pipe", "aborted",
    "cancelled", "preempt", "network error",
)


@dataclass(frozen=True)
class Status:
    """Operation status (reference: cpp/src/cylon/status.hpp).

    ``Status.OK()`` is success; anything else carries a code and message.
    """

    code: Code = Code.OK
    msg: str = ""

    @staticmethod
    def OK() -> "Status":
        return Status(Code.OK, "")

    @staticmethod
    def from_exception(exc: BaseException) -> "Status":
        """Classify an exception into the `Code` table.

        `CylonError` keeps its own code; `MemoryError` and PJRT
        ``RESOURCE_EXHAUSTED``/allocator text map to `Code.OutOfMemory`;
        deadline/comm failure text maps to retryable `Code.ExecutionError`;
        anything unrecognized is `Code.UnknownError` (never retried, never
        split — a TypeError must surface as the bug it is)."""
        if isinstance(exc, CylonError):
            return Status(exc.code, exc.msg)
        msg = f"{type(exc).__name__}: {exc}"
        if isinstance(exc, MemoryError):
            return Status(Code.OutOfMemory, msg)
        if isinstance(exc, (TimeoutError, ConnectionError)):
            return Status(Code.ExecutionError, msg)
        # message-text matching is for PJRT/XLA failures, which surface as
        # RuntimeError (XlaRuntimeError's base); on any other type the
        # text is a bug's wording — e.g. ValueError("... timed out") —
        # and must stay unknown, never retried or split
        if isinstance(exc, RuntimeError):
            low = str(exc).lower()
            if any(p in low for p in _OOM_PATTERNS):
                return Status(Code.OutOfMemory, msg)
            if any(p in low for p in _TRANSIENT_PATTERNS):
                return Status(Code.ExecutionError, msg)
        return Status(Code.UnknownError, msg)

    def is_ok(self) -> bool:
        return self.code == Code.OK

    def get_code(self) -> Code:
        return self.code

    def get_msg(self) -> str:
        return self.msg

    def __bool__(self) -> bool:
        return self.is_ok()


class CylonError(Exception):
    """Exception raised by the Python-first API when an operation fails.

    ``retry_after_s`` (serving layer, PR 7): on admission rejects
    (`Code.ResourceExhausted` / `Code.Unavailable` sheds) it carries the
    service's estimate of when capacity returns — the classified
    alternative to an unbounded wait.  None everywhere else."""

    def __init__(self, code: Code, msg: str,
                 retry_after_s: "float | None" = None):
        super().__init__(f"[{code.name}] {msg}")
        self.code = code
        self.msg = msg
        self.retry_after_s = retry_after_s


def raise_not_ok(status: Status) -> None:
    if not status.is_ok():
        raise CylonError(status.code, status.msg)
