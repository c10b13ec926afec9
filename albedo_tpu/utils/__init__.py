"""Utility layer: profiling/timing harness and schema assertions."""


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (and >= 1) — the shape-ladder rounding
    shared by the feature assembler's bag pads and the serving batcher's
    user-bucket/k quantization."""
    return 1 << max(0, int(n - 1).bit_length())


from albedo_tpu.utils.checkpoint import (  # noqa: E402
    Preempted,
    PreemptionHandler,
    StepCheckpointer,
    checkpointed_als_fit,
    restore_pytree,
    save_pytree,
)
from albedo_tpu.utils.faults import FaultInjected
from albedo_tpu.utils.profiling import Timer
from albedo_tpu.utils.retry import (
    RetriesExhausted,
    RetryAfter,
    RetryPolicy,
    retry_call,
)
from albedo_tpu.utils.schema import assert_columns, equals_ignore_nullability

__all__ = [
    "FaultInjected",
    "Preempted",
    "PreemptionHandler",
    "RetriesExhausted",
    "RetryAfter",
    "RetryPolicy",
    "StepCheckpointer",
    "Timer",
    "pow2_at_least",
    "assert_columns",
    "checkpointed_als_fit",
    "equals_ignore_nullability",
    "restore_pytree",
    "retry_call",
    "save_pytree",
]
