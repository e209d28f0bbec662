"""Typed serving errors — the defined failure modes under concurrent load.

The serving contract (docs/architecture.md §"Serving & admission control")
is that a query submitted through the admission gate has exactly three
outcomes: it completes bit-exact, it is **rejected** before consuming
device resources (:class:`QueryRejected`, with a retry-after hint so a
well-behaved client backs off instead of hammering), or it is **aborted**
when its latency budget expires (:class:`DeadlineExceeded`).  Nothing else
is a legal serving outcome — an untyped exception escaping the gate is a
bug, and the chaos acceptance suite (scripts/serving_smoke.py) asserts it.

These are *serving* decisions, deliberately disjoint from the
infrastructure classification in core/execution/resilience.py: a
``DeviceFailure`` means the accelerator runtime misbehaved; a
``ServingError`` means the system is protecting itself (or the caller's
budget) on purpose.  ``classify_device_error`` therefore never captures
them — they propagate through the engine seam untouched.
"""

from __future__ import annotations

from typing import Optional


class ServingError(RuntimeError):
    """Base for typed serving outcomes (admission control / deadlines)."""

    kind = "serving"


class QueryRejected(ServingError):
    """The admission gate refused the query before any work ran.

    ``reason`` is one of the shed causes (``queue_full``,
    ``tenant_throttled``, ``tenant_unhealthy``, ``queue_wait_deadline``);
    ``retry_after_s`` is the gate's estimate of when capacity returns —
    a load balancer maps it onto HTTP 429 + Retry-After.
    """

    kind = "rejected"

    def __init__(
        self, message: str, reason: str = "queue_full",
        retry_after_s: Optional[float] = None,
    ):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s


class DeadlineExceeded(ServingError):
    """The query's latency budget expired mid-flight and it was aborted.

    Raised at the seam boundaries the cancellation token is checked at
    (engine attempt start, retry/backoff sleeps, spill/evict passes,
    fused-chain materialization, plan lowering) — so the overshoot past
    the deadline is bounded by one engine attempt, never by the query's
    full runtime.  ``where`` names the seam that observed expiry.
    """

    kind = "deadline"

    def __init__(
        self, message: str, deadline_s: float = 0.0, where: str = "",
    ):
        super().__init__(message)
        self.deadline_s = deadline_s
        self.where = where
