"""Status / error types and dtype tables.

Parity with the reference's ``horovod/common/common.h``:
``Status`` kinds (``common.h:122-136``), the error kinds surfaced to
users (duplicate names ``common.h:161``, crashed-rank semantics
``common.h:154-159``), and the supported dtype table.  On TPU, dtypes
map to JAX/XLA dtypes rather than framework enums; bfloat16 is
first-class (the MXU's native accumulation format) where the reference
special-cases IEEE fp16 (``horovod/common/half.h``).
"""

from __future__ import annotations

import enum

import jax.numpy as jnp
import numpy as np


class StatusType(enum.Enum):
    OK = 0
    UNKNOWN_ERROR = 1
    PRECONDITION_ERROR = 2
    ABORTED = 3
    INVALID_ARGUMENT = 4
    IN_PROGRESS = 5


class Status:
    """Result of an enqueued operation (reference ``common.h:122-152``).

    ``exc_class`` optionally names the exception type a waiting user
    thread should raise (e.g. :class:`RanksDownError` after a
    coordinated abort) so failure causes stay diagnosable through the
    handle layer instead of collapsing into a generic error."""

    __slots__ = ("type", "reason", "exc_class")

    def __init__(self, type_: StatusType = StatusType.OK, reason: str = "",
                 exc_class: type | None = None):
        self.type = type_
        self.reason = reason
        self.exc_class = exc_class

    @staticmethod
    def ok() -> "Status":
        return Status(StatusType.OK)

    @staticmethod
    def unknown(msg: str) -> "Status":
        return Status(StatusType.UNKNOWN_ERROR, msg)

    @staticmethod
    def precondition(msg: str, exc_class: type | None = None) -> "Status":
        return Status(StatusType.PRECONDITION_ERROR, msg, exc_class)

    @staticmethod
    def aborted(msg: str, exc_class: type | None = None) -> "Status":
        return Status(StatusType.ABORTED, msg, exc_class)

    @staticmethod
    def invalid_argument(msg: str) -> "Status":
        return Status(StatusType.INVALID_ARGUMENT, msg)

    @staticmethod
    def in_progress() -> "Status":
        return Status(StatusType.IN_PROGRESS)

    def ok_p(self) -> bool:
        return self.type == StatusType.OK

    def in_progress_p(self) -> bool:
        return self.type == StatusType.IN_PROGRESS

    def __repr__(self) -> str:
        return f"Status({self.type.name}, {self.reason!r})"


class HorovodTpuError(RuntimeError):
    """Base error surfaced to user threads."""


class HorovodInternalError(HorovodTpuError):
    """Collective failed after enqueue (analog of the reference's
    exception raised from ``synchronize``)."""


class TensorShapeMismatchError(HorovodTpuError):
    """Coordinator-validated mismatch: same tensor name submitted with
    different shapes on different ranks (reference ``controller.cc:477-533``)."""


class DuplicateNameError(HorovodTpuError):
    """Same tensor name submitted twice before completion
    (reference ``common.h:161``, ``tensor_queue.cc``)."""


class StalledError(HorovodTpuError):
    """Stall inspector escalation (reference ``stall_inspector.h:74-80``)."""


class RanksDownError(HorovodTpuError):
    """One or more peer ranks stopped heartbeating and the job was
    coordinately aborted (the crashed-rank semantics the reference
    documents at ``common.h:154-159``, made prompt: survivors fail
    within ``HOROVOD_HEARTBEAT_TIMEOUT_SECONDS`` instead of hanging in
    a wire timeout).  Carries which ranks died, the negotiation round
    the abort fired in, and how long the heartbeats had been stale.

    Abort messages open with ``WIRE_PREFIX`` followed by a JSON header
    (``{"ranks": [...], "round": r, "elapsed": s, ...}``); when the
    structured fields aren't passed explicitly — the exception is
    often rebuilt from just the message after riding a wire Response
    or a handle Status — they are rehydrated from that header."""

    WIRE_PREFIX = "RanksDownError:"

    def __init__(self, msg: str, ranks: tuple = (), round: int = -1,
                 elapsed: float = 0.0):
        super().__init__(msg)
        if not ranks and msg.startswith(self.WIRE_PREFIX):
            try:
                import json

                blob = msg[len(self.WIRE_PREFIX):].strip()
                meta = json.loads(blob[:blob.index("}") + 1])
                ranks = tuple(meta.get("ranks", ()))
                round = int(meta.get("round", round))
                elapsed = float(meta.get("elapsed", elapsed))
            except (ValueError, TypeError):
                pass
        self.ranks = tuple(ranks)
        self.round = round
        self.elapsed = elapsed


class JoinedRankError(HorovodTpuError):
    """Operation submitted after this rank joined."""


# Supported wire dtypes (reference Request dtype field, message.h:47-100).
SUPPORTED_DTYPES = (
    jnp.uint8,
    jnp.int8,
    jnp.uint16,
    jnp.int16,
    jnp.int32,
    jnp.int64,
    jnp.float16,
    jnp.bfloat16,
    jnp.float32,
    jnp.float64,
    jnp.bool_,
)

_DTYPE_CODES = {np.dtype(d): i for i, d in enumerate(SUPPORTED_DTYPES)}
_CODE_DTYPES = {i: np.dtype(d) for i, d in enumerate(SUPPORTED_DTYPES)}


def dtype_code(dtype) -> int:
    """Stable small-int code for a dtype (wire format for negotiation)."""
    d = np.dtype(dtype)
    if d not in _DTYPE_CODES:
        raise HorovodTpuError(f"Unsupported dtype for collective: {dtype}")
    return _DTYPE_CODES[d]


def dtype_from_code(code: int):
    return _CODE_DTYPES[code]
