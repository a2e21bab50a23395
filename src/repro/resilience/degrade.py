"""Graceful dispatch degradation: bounded retry + resident demotion.

One compiled-call launch travels through :func:`run_dispatch`, which
classifies failures by *recoverability* (DESIGN.md S13):

* **transient** (:func:`is_transient` -- ``TransientDispatchError`` or
  an XLA ``UNAVAILABLE``/``DEADLINE_EXCEEDED`` status) -- retried with
  exponential backoff under a bounded :class:`RetryPolicy`; each retry
  increments the ``resilience.retry`` counter and emits a
  ``resilience.retry`` trace instant.
* **resident-tier resource exhaustion** (:func:`is_resident_oom` -- an
  ``XlaRuntimeError``-style launch failure carrying
  ``RESOURCE_EXHAUSTED``) -- the (engine family, lattice) is *demoted* to the
  per-half-sweep fallback tier for the rest of the process and the
  launch retried immediately.  Both tiers draw the same Philox stream
  (tests/test_resident.py), so demotion is invisible in the
  trajectory; it costs one re-JIT and O(k) extra HBM traffic.
* anything else propagates unchanged -- including Mosaic refusing a
  kernel at compile time (:func:`is_compile_refusal`: a working set over
  the scoped-VMEM limit also says ``RESOURCE_EXHAUSTED``).  That is a
  planner bug (``kernels/resident.py``), and running the fallback tier
  in silence would hide it.

Demotions live in a process-global registry keyed ``(family, n, m)``:
``kernels.resident.plan_resident`` and ``decision_attrs`` consult it,
so engine construction, ``--dry-run`` plans, and dispatch span
attributes all agree that a demoted lattice runs the fallback tier.

Injected faults (``repro.resilience.faults``) are checked BEFORE the
compiled call is invoked, so a failed launch never consumes donated
input buffers and retrying with the same state is always safe.  With
no fault plan installed and no failure raised, ``run_dispatch`` adds
one ``is None`` load to the hot path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Tuple

import repro.telemetry as tel

from . import faults
from .errors import TransientDispatchError

#: recovery counters -- module-held references survive REGISTRY.reset()
RETRIES = tel.REGISTRY.counter("resilience.retry")
DEMOTIONS = tel.REGISTRY.counter("resident.demote")

#: XLA status tokens worth a bounded retry (transport/queue hiccups)
_TRANSIENT_TOKENS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED")


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff for transient dispatch failures.

    ``sleep`` is injectable so tests retry without wall-clock cost.
    """

    max_retries: int = 3
    base_delay_s: float = 0.05
    multiplier: float = 4.0
    max_delay_s: float = 5.0
    sleep: Callable[[float], None] = time.sleep

    def delay(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (0-based)."""
        return min(self.base_delay_s * self.multiplier ** attempt,
                   self.max_delay_s)


DEFAULT_POLICY = RetryPolicy()


def is_transient(exc: BaseException) -> bool:
    """Worth retrying: our typed transient error, or an XLA runtime
    failure whose status token marks the *attempt* (not the program)
    as the problem."""
    if isinstance(exc, TransientDispatchError):
        return True
    msg = str(exc)
    return any(tok in msg for tok in _TRANSIENT_TOKENS)


#: phrases of Mosaic's compile-time scoped-VMEM refusal
_COMPILE_REFUSAL_TOKENS = ("memory space vmem", "scoped vmem")


def is_compile_refusal(exc: BaseException) -> bool:
    """Mosaic refused to compile a kernel for its VMEM working set."""
    msg = str(exc).lower()
    return any(tok in msg for tok in _COMPILE_REFUSAL_TOKENS)


def is_resident_oom(exc: BaseException) -> bool:
    """A runtime resource-exhaustion failure (real XLA OOM or the
    injected stand-in): recoverable by demoting the resident tier, NOT
    by retrying the same program.  A compile refusal is not one."""
    return "RESOURCE_EXHAUSTED" in str(exc) and not is_compile_refusal(exc)


# ---------------------------------------------------------------------------
# demotion registry: (family, n, m) -> reason, process-global
# ---------------------------------------------------------------------------

_DEMOTED: Dict[Tuple[str, int, int], str] = {}


def demote(family: str, n: int, m: int, reason: str) -> None:
    """Record that (family, n, m) must run the fallback tier from now
    on.  Idempotent; the first reason wins."""
    _DEMOTED.setdefault((family, n, m), reason)


def demotion_reason(family: str, n: int, m: int) -> Optional[str]:
    """The recorded demotion reason, or ``None`` when not demoted."""
    return _DEMOTED.get((family, n, m))


def demotions() -> Dict[Tuple[str, int, int], str]:
    """Snapshot of the registry (copy; mutating it changes nothing)."""
    return dict(_DEMOTED)


def reset_demotions() -> None:
    """Forget every demotion -- test isolation, not production use."""
    _DEMOTED.clear()


def _engine_demotable(engine) -> bool:
    return getattr(engine, "resident_plan", None) is not None


def run_dispatch(attempt: Callable[[], object], *, engine=None,
                 on_demote: Optional[Callable[[], None]] = None,
                 policy: Optional[RetryPolicy] = None):
    """Run one compiled-call launch with recovery (module docstring).

    ``attempt`` is a zero-arg closure over the launch; it is re-invoked
    as-is on retry, and after a demotion it must observe the engine's
    new tier (the engine wrappers re-read ``self.resident_plan`` /
    their jit caches on every call, so a plain closure does).
    ``on_demote`` lets callers owning their own jit caches (the batched
    runners) invalidate them when the engine's tier changes.
    """
    policy = DEFAULT_POLICY if policy is None else policy
    retries = 0
    while True:
        plan = faults.active_plan()
        try:
            if plan is not None:
                plan.maybe_fail_dispatch(_engine_demotable(engine))
            return attempt()
        except Exception as exc:
            if (engine is not None and _engine_demotable(engine)
                    and is_resident_oom(exc)):
                DEMOTIONS.inc()
                tel.instant("resident.demote", engine=engine.name,
                            lattice=(engine.cfg.n, engine.cfg.m),
                            reason=str(exc))
                engine._demote_resident(str(exc))
                if on_demote is not None:
                    on_demote()
                continue  # immediate retry on the fallback tier
            if is_transient(exc) and retries < policy.max_retries:
                delay = policy.delay(retries)
                retries += 1
                RETRIES.inc()
                tel.instant("resilience.retry", attempt=retries,
                            max_retries=policy.max_retries,
                            delay_s=delay, error=str(exc))
                policy.sleep(delay)
                continue
            raise
