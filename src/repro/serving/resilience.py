"""Fault injection, request-level containment and crash recovery for the
serving engine.

The source paper's argument — decouple the ISA *contract* from the
microarchitecture so software adapts dynamically — has a systems
analogue this module implements: decouple the request-lifecycle contract
from the engine internals so requests fail, shed and recover
*individually* while the batched decode keeps running.  Four pieces:

- **error taxonomy** — :class:`RequestError` and its subclasses
  (:class:`DeadlineExceeded`, :class:`Shed`, :class:`PoisonedOutput`,
  :class:`CapacityExceeded`) name every way a request can end other
  than normal completion.  Each carries a stable ``code`` string the
  engine stamps into the request's :class:`Response`.
- **Response** — what ``ServingEngine.run()`` returns per request:
  the generated tokens plus a structured ``status``/``error`` and a
  small metrics dict.  It subclasses ``list`` so every existing
  consumer of the old bare token list (``len``, slicing, equality)
  keeps working unchanged; new consumers read ``.status``.
- **FaultInjector** — a *seeded, deterministic* chaos harness threaded
  through the engine's hooks.  A fault plan is an explicit list of
  :class:`Fault` specs (or a seeded random plan): page-allocation
  failure, chunk-compute exception, NaN/inf-poisoned logits on a chosen
  request/step, a straggling step, a mid-run crash.  The injector logs
  every firing (``.fired``) so chaos tests can assert same seed → same
  faults → same outputs.
- **crash recovery** — :func:`serve_with_recovery` runs an engine under
  ``repro.distributed.fault.supervise``: a crash (or a watchdog-detected
  straggler) snapshots the engine's host-side state
  (``ServingEngine.snapshot()``), rebuilds a fresh engine and restores
  (``restore()``) — in-flight requests are re-admitted through the
  PR-5 prefix-cache re-attachment path, so KV is recomputed only where
  pages were never published.

Nothing here imports the engine — the engine imports *this* module, and
:func:`serve_with_recovery` receives an engine factory.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

__all__ = [
    "RequestError", "DeadlineExceeded", "Shed", "PoisonedOutput",
    "CapacityExceeded", "EngineCrash", "Response", "Fault",
    "FaultInjector", "serve_with_recovery",
]


# -- error taxonomy -----------------------------------------------------------


class RequestError(RuntimeError):
    """A request ended abnormally.  ``code`` is the stable status string
    stamped into the request's :class:`Response` (subclasses override)."""

    code = "error"

    def __init__(self, message: str = "", *, rid: Optional[int] = None):
        super().__init__(message or self.__class__.__name__)
        self.rid = rid


class DeadlineExceeded(RequestError):
    """The request's deadline passed before it finished; partial output
    is returned with this status."""

    code = "deadline"


class Shed(RequestError):
    """Admission control rejected the request at ``submit`` (queue depth
    or committed-token watermark exceeded) — backpressure instead of
    unbounded queue growth."""

    code = "shed"


class PoisonedOutput(RequestError):
    """The request's logits went NaN/inf; the slot was quarantined and
    cancelled while the rest of the batch kept decoding."""

    code = "poisoned"


class CapacityExceeded(RequestError):
    """The request can never be admitted (pool or token budget too small
    for it alone) — cancelled individually instead of wedging the
    engine."""

    code = "capacity"


class EngineCrash(RuntimeError):
    """An injected (or real) engine-level crash — the supervised-restart
    path's trigger, distinct from any per-request error."""


# -- structured per-request result --------------------------------------------


class Response(list):
    """Generated tokens + completion status for one request.

    Subclasses ``list`` (of int token ids) so existing consumers of the
    old ``Dict[int, List[int]]`` return shape — ``len(resp)``,
    ``resp[:8]``, ``resp == [..]`` — keep working; status-aware callers
    read ``.status`` (``"ok"``, ``"incomplete"``, or a
    :class:`RequestError` code), ``.error`` and ``.metrics``.
    """

    def __init__(self, tokens: Sequence[int] = (), *, rid: int,
                 status: str = "ok", error: Optional[RequestError] = None,
                 metrics: Optional[dict] = None):
        super().__init__(int(t) for t in tokens)
        self.rid = int(rid)
        self.status = status
        self.error = error
        self.metrics: Dict[str, float] = dict(metrics or {})

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def tokens(self) -> List[int]:
        return list(self)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Response(rid={self.rid}, status={self.status!r}, "
                f"tokens={list(self)})")


# -- deterministic fault injection --------------------------------------------

FAULT_KINDS = ("alloc_fail", "chunk_exception", "poison_logits",
               "straggle", "crash")


@dataclasses.dataclass(frozen=True)
class Fault:
    """One planned fault.

    ``kind`` selects the failure class; the optional trigger fields
    narrow *when* it fires: ``step`` (engine step index; ``None`` = the
    first opportunity), ``rid`` (target request for poison/chunk
    faults), ``chunk`` (chunk index for chunk faults).  ``count`` caps
    how many times it fires (an injector survives an engine restart, so
    a ``count=1`` crash does not re-fire on the restarted engine).
    """

    kind: str
    step: Optional[int] = None
    rid: Optional[int] = None
    chunk: Optional[int] = None
    count: int = 1
    value: float = float("nan")   # poison payload (nan / inf)
    delay_s: float = 0.0          # straggle duration

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"expected one of {FAULT_KINDS}")


class FaultInjector:
    """Seeded, deterministic fault plan executor.

    The engine calls the hooks; the injector decides — purely from the
    plan and its own firing history — whether a fault triggers.  Every
    firing is appended to ``self.fired`` as ``(step, kind, target)`` so
    tests can assert reproducibility: same plan (or same seed) → same
    firings → same outputs.
    """

    def __init__(self, faults: Sequence[Fault] = (), seed: int = 0):
        self.faults: List[Fault] = list(faults)
        self.seed = int(seed)
        self._remaining = [max(0, int(f.count)) for f in self.faults]
        self.fired: List[tuple] = []

    # -- plan construction -----------------------------------------------------
    @classmethod
    def random_plan(cls, seed: int, *, n_faults: int = 3, max_step: int = 16,
                    rids: Sequence[int] = (0, 1, 2, 3),
                    kinds: Sequence[str] = ("alloc_fail", "poison_logits",
                                            "chunk_exception")
                    ) -> "FaultInjector":
        """A deterministic plan drawn from ``seed`` — the chaos-suite
        entry point (crash/straggle are opt-in: they need a supervisor)."""
        rng = np.random.default_rng(seed)
        faults = []
        for _ in range(n_faults):
            kind = str(rng.choice(list(kinds)))
            faults.append(Fault(
                kind=kind,
                step=int(rng.integers(1, max_step)),
                rid=int(rng.choice(list(rids))),
                chunk=None,
                value=float(rng.choice([np.nan, np.inf, -np.inf])),
            ))
        return cls(faults, seed=seed)

    @classmethod
    def from_spec(cls, spec: str) -> "FaultInjector":
        """Parse a compact CLI plan: ``kind[:k=v[,k=v...]][;kind...]``,
        e.g. ``poison_logits:rid=0,step=5;straggle:step=3,delay_s=0.5``.
        """
        faults = []
        for part in filter(None, (p.strip() for p in spec.split(";"))):
            kind, _, argstr = part.partition(":")
            kw: Dict[str, object] = {}
            for item in filter(None, (a.strip() for a in argstr.split(","))):
                key, _, val = item.partition("=")
                if key in ("step", "rid", "chunk", "count"):
                    kw[key] = int(val)
                elif key in ("value", "delay_s"):
                    kw[key] = float(val)
                else:
                    raise ValueError(f"unknown fault field {key!r} in "
                                     f"{part!r}")
            faults.append(Fault(kind=kind.strip(), **kw))
        return cls(faults)

    # -- firing machinery ------------------------------------------------------
    def _take(self, i: int, step: int, target) -> bool:
        if self._remaining[i] <= 0:
            return False
        self._remaining[i] -= 1
        kind = self.faults[i].kind
        self.fired.append((int(step), kind, target))
        # Mark the firing on the installed trace (repro.telemetry) so a
        # chaos run replays as a timeline: the fault instant sits between
        # the engine-phase spans it perturbed.
        from repro.telemetry import tracing
        tr = tracing.active()
        if tr is not None:
            tr.instant(f"fault.{kind}",
                       args={"step": int(step), "target": repr(target)})
        return True

    def _matches(self, f: Fault, *, step: int, rid: Optional[int] = None,
                 chunk: Optional[int] = None) -> bool:
        if f.step is not None and step < f.step:
            return False
        if f.rid is not None and rid is not None and f.rid != rid:
            return False
        if f.chunk is not None and chunk is not None and f.chunk != chunk:
            return False
        return True

    # -- engine hooks ----------------------------------------------------------
    def step_begin(self, step: int, pool=None) -> None:
        """Engine-step preamble: crashes, stragglers and page-allocation
        failures fire here.  ``pool`` (a ``KVPagePool``) receives the
        alloc-failure injection as a consume-once counter its next
        ``ensure``/``admit_prefix`` honours."""
        for i, f in enumerate(self.faults):
            if f.kind == "straggle" and self._matches(f, step=step) \
                    and self._remaining[i] > 0:
                self._take(i, step, None)
                time.sleep(f.delay_s)
            elif f.kind == "alloc_fail" and pool is not None \
                    and self._matches(f, step=step) and self._remaining[i] > 0:
                self._take(i, step, None)
                pool.inject_alloc_failures += 1
            elif f.kind == "crash" and self._matches(f, step=step) \
                    and self._remaining[i] > 0:
                self._take(i, step, None)
                raise EngineCrash(f"injected crash at step {step}")

    def chunk_fault(self, step: int, rid: int, chunk: int) -> None:
        """Raises the injected chunk-compute exception when armed for
        this (request, chunk)."""
        for i, f in enumerate(self.faults):
            if f.kind == "chunk_exception" \
                    and self._matches(f, step=step, rid=rid, chunk=chunk) \
                    and self._remaining[i] > 0:
                self._take(i, step, (rid, chunk))
                raise RequestError(
                    f"injected chunk-compute fault (rid={rid}, "
                    f"chunk={chunk})", rid=rid)

    def poison_value(self, step: int, rid: int) -> Optional[float]:
        """The NaN/inf payload to overwrite ``rid``'s logits with at
        this decode step, or None."""
        for i, f in enumerate(self.faults):
            if f.kind == "poison_logits" \
                    and self._matches(f, step=step, rid=rid) \
                    and self._remaining[i] > 0:
                self._take(i, step, rid)
                return f.value
        return None


# -- supervised serving (crash / straggler recovery) ---------------------------


def serve_with_recovery(make_engine: Callable[[], object],
                        requests: Sequence[object], *,
                        max_restarts: int = 3, backoff_s: float = 0.0,
                        keep_cache: bool = True,
                        log=print) -> Dict[int, Response]:
    """Run ``requests`` on a supervised engine with snapshot/restore.

    ``make_engine`` builds a fresh :class:`~repro.serving.engine.
    ServingEngine` (same params/config each time).  The first attempt
    submits ``requests``; on any failure (an :class:`EngineCrash`, a
    watchdog :class:`~repro.distributed.fault.StragglerError`, …) the
    dying engine's host-side state is snapshotted and the next attempt
    restores it — completed responses are carried over, in-flight and
    waiting requests are re-admitted, and with ``keep_cache=True`` the
    surviving device cache plus the snapshot's page registrations let
    the prefix cache re-attach published KV instead of recomputing it.
    Returns the final response dict.
    """
    from repro.distributed.fault import supervise

    state: Dict[str, object] = {"snap": None, "cache": None, "out": None}

    def attempt(i: int) -> None:
        eng = make_engine()
        if state["snap"] is not None:
            eng.restore(state["snap"],
                        cache=state["cache"] if keep_cache else None)
        else:
            for req in requests:
                eng.submit(req)
        try:
            state["out"] = eng.run()
        except Exception:
            state["snap"] = eng.snapshot()
            # The decode and chunk programs take the pool donated: a
            # failure raised inside one leaves ``eng.cache`` naming the
            # consumed buffers, and the restart then takes a fresh pool.
            consumed = any(leaf.is_deleted()
                           for leaf in jax.tree.leaves(eng.cache))
            state["cache"] = None if consumed else eng.cache
            raise

    supervise(attempt, max_restarts=max_restarts, backoff_s=backoff_s,
              log=log)
    return state["out"]  # type: ignore[return-value]
