"""Typed exceptions of the resilience subsystem (DESIGN.md S13).

The dispatch-recovery layer (``repro.resilience.degrade``) classifies
failures by *recoverability*, not by origin: a transient fault is worth
retrying with backoff, a resident-tier resource exhaustion is worth a
one-time demotion to the per-half-sweep fallback tier, and anything
else propagates.  The fault-injection harness
(``repro.resilience.faults``) raises exactly these types so injected
and real failures travel the same recovery paths.
"""
from __future__ import annotations


class ResilienceError(RuntimeError):
    """Base class of the resilience subsystem's own failures."""


class TransientDispatchError(ResilienceError):
    """A dispatch failure worth retrying: the operation itself is fine,
    the attempt hit a transient condition (queue full, device busy,
    injected chaos).  Classified transient by
    :func:`repro.resilience.degrade.is_transient`."""


class SimulatedResourceExhausted(ResilienceError):
    """Injected stand-in for an XLA ``RESOURCE_EXHAUSTED`` launch failure
    (the runtime out-of-memory class a resident dispatch can hit).  The
    message carries the literal ``RESOURCE_EXHAUSTED`` token so the
    classifier treats real and simulated failures identically."""

    def __init__(self, detail: str = "simulated device memory exhaustion"):
        super().__init__(f"RESOURCE_EXHAUSTED: {detail} (injected by "
                         f"repro.resilience.faults)")


class SupervisorError(ResilienceError):
    """A supervised run cannot proceed (no spec and no checkpoint, spec
    mismatch against the checkpoint being resumed, ...)."""


class FaultPlanError(ResilienceError):
    """A fault plan cannot be parsed (malformed JSON, not an object,
    unknown fault kind, negative count).  Carries the offending text so
    a bad ``REPRO_FAULTS`` value is diagnosable from the message alone
    -- a chaos job that silently runs WITHOUT its injected faults would
    pass vacuously."""

    def __init__(self, detail: str, text: str = ""):
        self.text = text
        suffix = f" (offending text: {text!r})" if text else ""
        super().__init__(f"bad fault plan: {detail}{suffix}")
