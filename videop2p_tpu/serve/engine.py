"""EditEngine: the persistent in-process edit-serving core.

Request lifecycle (one worker thread owns every device dispatch, so JAX
program order is deterministic and the HTTP layer never touches devices):

  admit → resolve (controller + content-addressed inversion-store lookup;
  a miss first tries LAZY REHYDRATION from the store's disk layer — a
  restarted engine rebuilds the device products from the persisted
  trajectory through its warm inversion program, no frame IO / VAE encode
  / cold compile — and only then runs VAE encode + capture-inversion ONCE
  per clip) → batch (compatible concurrent requests group into one
  dispatch, :mod:`videop2p_tpu.serve.batching`, formed by the PLUGGABLE
  scheduling policy — :mod:`videop2p_tpu.serve.sched`: ``drain`` is the
  bit-exact plan-boundary baseline, ``continuous`` admits mid-flight
  requests into the next dispatch, ``fair`` runs per-tenant
  deficit-round-robin lanes) → dispatch (the warm ``serve_edit`` program:
  cached-source controlled edit + VAE decode) → artifacts (GIFs) +
  per-request verdicts (``src_err``, compile-event delta, store hit,
  ``queue_wait_s``).

Resilience layer (ISSUE 9 — see ``docs/SERVING.md`` "Failure semantics"):

  * **deadlines** — per-request ``deadline_s`` admitted at submit; an
    expired request fails with terminal status ``deadline_exceeded``
    before any further device work is spent on it.
  * **watchdog** — the worker's device dispatch runs under a bounded
    block-until-ready (``dispatch_timeout_s`` and/or the batch's tightest
    remaining deadline); a dispatch that exceeds its budget fails the
    batch with ``deadline_exceeded`` instead of wedging the engine — the
    worker abandons the stuck thread and keeps serving.
  * **retry + circuit breaker** — transient dispatch failures retry on a
    capped, jitter-free exponential schedule
    (:class:`~videop2p_tpu.serve.faults.RetryPolicy`); consecutive batch
    failures trip the :class:`~videop2p_tpu.serve.faults.CircuitBreaker`
    (closed → open → half-open): while open, submits fast-fail 503 with
    ``Retry-After`` and ``/healthz`` reports ``degraded``; recovery is
    automatic when the half-open probe dispatch succeeds.
  * **backpressure** — a bounded admit queue (``max_queue`` in-flight);
    over it, submits raise :class:`~videop2p_tpu.serve.faults.QueueFull`
    (HTTP 429 with the queue depth in the body).
  * **fault injection** — a deterministic
    :class:`~videop2p_tpu.serve.faults.FaultPlan` threads through the
    dispatch and store seams so every behavior above is testable on CPU.

Observability is the live run ledger: the engine owns an activated
:class:`~videop2p_tpu.obs.RunLedger` with execute timing ON, so every
program dispatch lands in the per-program latency reservoirs
(:mod:`videop2p_tpu.obs.timing`), every compile is attributed, and every
injected fault / breaker transition becomes a ``fault`` / ``breaker``
event; closing the engine writes one ``serve_health`` summary gated by
``FAULT_RULES`` through ``tools/obs_diff.py`` like any other run record.

Cost & capacity plane (ISSUE 19 — :mod:`videop2p_tpu.obs.cost`): every
successful dispatch is priced by fair share over its padded slots, so
terminal ``done`` records carry a per-request ``cost`` vector
(device/queue seconds, attributed flops and HBM-byte-seconds, padding
share; store hits credited the avoided inversion), ``/metrics`` grows a
``capacity`` section (busy/idle fraction, padding waste, occupancy) and
close() emits per-tenant/per-program ``cost_attribution`` chargeback
rows with the conservation invariant attributed + padding = busy, idle
explicit — gated by ``COST_RULES``.

Stdlib+numpy+jax only — the import-guard test walks this package.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from videop2p_tpu.serve.batching import (
    compat_key,
    stack_items,
    unstack_outputs,
)
from videop2p_tpu.serve.sched import (
    Scheduler,
    TenantConfig,
    make_scheduler,
    parse_tenants,
)
from videop2p_tpu.serve.faults import (
    CircuitBreaker,
    DeadlineExceeded,
    EngineUnavailable,
    FaultPlan,
    QueueFull,
    RetryPolicy,
    is_transient,
)
from videop2p_tpu.obs.cost import CostModel
from videop2p_tpu.obs.probe import PROBE_TENANT
from videop2p_tpu.obs.spans import (
    Tracer,
    make_span_id,
    make_trace_id,
    span,
    parse_traceparent,
)
from videop2p_tpu.serve.programs import ProgramSet, ProgramSpec
from videop2p_tpu.serve.store import InversionStore

__all__ = ["EditRequest", "EditEngine", "TERMINAL_STATUSES"]

_REQUEST_FIELDS = (
    "image_path", "prompt", "prompts", "save_name", "is_word_swap",
    "blend_word", "eq_params", "cross_replace_steps", "self_replace_steps",
    "seed", "steps", "deadline_s", "tenant", "quant_mode", "reuse_schedule",
    "student",
)

# the machine-readable terminal statuses — everything else is in flight.
# "error": the engine gave up on the request (resolve failure, retries
# exhausted); "deadline_exceeded": its budget expired (queued too long or
# the dispatch watchdog fired); "engine_closed": close() drained it.
TERMINAL_STATUSES = ("done", "error", "deadline_exceeded", "engine_closed")

# bounded in-memory mirror of the fault/breaker ledger events — /metrics
# and the chaos loadgen read it without re-parsing the ledger file
_FAULT_LOG_MAX = 256


@dataclass
class EditRequest:
    """One edit of one clip — the JSON surface of the HTTP API.

    ``frames`` (host array, (F, H, W, 3) uint8) may replace ``image_path``
    for in-process callers; it never crosses the JSON boundary.
    """

    image_path: str = ""
    prompt: str = ""
    prompts: Sequence[str] = field(default_factory=list)
    save_name: str = "edit"
    is_word_swap: bool = False
    blend_word: Optional[Sequence[str]] = None
    eq_params: Optional[Dict] = None
    cross_replace_steps: float = 0.2
    self_replace_steps: float = 0.5
    seed: int = 0
    # per-request DDIM step count (the latency-vs-quality knob): None = the
    # spec's base count; fewer steps run the timestep-subset fast path from
    # the SAME base-steps inversion products. Must be a warmed bucket —
    # the engine rejects unknown step geometry at admission (HTTP 400)
    # rather than compiling cold mid-serve.
    steps: Optional[int] = None
    # per-request latency budget in seconds, measured from submit: the
    # request fails with terminal status "deadline_exceeded" once it
    # expires (queued, resolving or mid-dispatch — the dispatch watchdog
    # bounds the block-until-ready). None = the engine default.
    deadline_s: Optional[float] = None
    # QoS identity: the fair scheduler's lane, the per-tenant deadline
    # default (TenantConfig), and the per-tenant accounting in
    # serve_health / /metrics all key on this; "" → "default"
    tenant: str = "default"
    # per-call cost levers (ISSUE 15). quant_mode is an ASSERTION, not a
    # request: weights are quantized at program-set build, so the engine
    # rejects any value other than the set's own mode at admission (HTTP
    # 400 naming the served mode). reuse_schedule selects a warmed
    # cross-step deep-feature reuse schedule; like steps, unknown
    # schedules are rejected at admission (400 with the warmed list)
    # rather than compiling a cold scan body mid-serve. None = the spec's
    # defaults.
    quant_mode: Optional[str] = None
    reuse_schedule: Optional[str] = None
    # run the consistency-distilled few-step student (ISSUE 16): the
    # distilled params + time-conditioning head serve this request over
    # the same teacher inversion products. Admitted only when the set was
    # built with a student_ckpt AND the resolved step count is a warmed
    # student bucket — otherwise 400 listing the warmed options.
    student: bool = False
    frames: Optional[np.ndarray] = None

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "EditRequest":
        unknown = set(d) - set(_REQUEST_FIELDS)
        if unknown:
            raise ValueError(f"unknown request field(s): {sorted(unknown)}")
        return cls(**d)

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in _REQUEST_FIELDS}

    def validate(self) -> None:
        if not self.prompt:
            raise ValueError("request needs a source 'prompt'")
        if len(list(self.prompts)) < 2:
            raise ValueError(
                "request needs 'prompts' = [source, edit, ...] (>= 2 entries)"
            )
        if list(self.prompts)[0] != self.prompt:
            raise ValueError("prompts[0] must equal the source prompt")
        if self.frames is None and not self.image_path:
            raise ValueError("request needs 'image_path' (or in-process frames)")
        if self.steps is not None and (not isinstance(self.steps, int)
                                       or self.steps < 1):
            raise ValueError(f"'steps' must be a positive int, got {self.steps!r}")
        if self.deadline_s is not None and (
            not isinstance(self.deadline_s, (int, float))
            or isinstance(self.deadline_s, bool) or self.deadline_s <= 0
        ):
            raise ValueError(
                f"'deadline_s' must be positive seconds, got {self.deadline_s!r}"
            )
        if self.tenant is not None and not isinstance(self.tenant, str):
            raise ValueError(f"'tenant' must be a string, got {self.tenant!r}")
        if self.quant_mode is not None:
            from videop2p_tpu.models.quant import validate_quant_mode

            validate_quant_mode(self.quant_mode)
        if self.reuse_schedule is not None and not isinstance(
            self.reuse_schedule, str
        ):
            raise ValueError(
                f"'reuse_schedule' must be a string, got {self.reuse_schedule!r}"
            )
        if not isinstance(self.student, bool):
            raise ValueError(
                f"'student' must be a bool, got {self.student!r}"
            )


@dataclass(eq=False)
class _Prepared:
    """A resolved request, ready to batch: the device argument tree plus
    its batching-compatibility key, resolved step count, and the
    scheduling metadata the pluggable policies order on (submit sequence,
    arrival clock, deadline, tenant lane)."""

    rid: str
    args: Tuple  # (cached, cond_all, uncond, ctx, anchor)
    compat: str
    steps: int
    reuse: str = "off"
    student: bool = False
    seq: int = 0
    arrival_s: float = 0.0
    deadline_at: Optional[float] = None
    tenant: str = "default"


class EditEngine:
    """Persistent multi-tenant edit engine over one :class:`ProgramSet`."""

    def __init__(
        self,
        spec: ProgramSpec,
        *,
        out_dir: str,
        store_budget_bytes: int = 4 << 30,
        persist_dir: Optional[str] = None,
        max_batch: int = 4,
        max_wait_s: float = 0.05,
        batch_dispatch: str = "scan",
        ledger_path: Optional[str] = None,
        keep_videos: bool = False,
        programs: Optional[ProgramSet] = None,
        # scheduling policy (ISSUE 11 — serve/sched.py): "drain" is the
        # pre-scheduler engine pinned bit-exact; "continuous" admits
        # compatible requests into the next dispatch; "fair" runs
        # per-tenant DRR lanes. Also accepts a Scheduler instance.
        scheduler: Any = "drain",
        # per-tenant QoS config: {name: TenantConfig} or the CLI spec
        # string ("A:5,B:1" / JSON) — weights/priorities for the fair
        # policy plus per-tenant default deadline budgets
        tenants: Any = None,
        # drain-policy latency knobs (defaults keep it bit-exact): cap the
        # admit window by the first request's total time-in-queue, and
        # dispatch planned chunks by oldest-member arrival
        max_batch_wait_s: Optional[float] = None,
        batch_order: str = "first_seen",
        # resilience knobs (docs/SERVING.md "Failure semantics")
        max_queue: int = 64,
        default_deadline_s: Optional[float] = None,
        dispatch_timeout_s: Optional[float] = None,
        max_retries: int = 2,
        retry_base_s: float = 0.05,
        retry_cap_s: float = 2.0,
        breaker_threshold: int = 3,
        breaker_open_s: float = 5.0,
        faults: Optional[FaultPlan] = None,
        # observability knobs (ISSUE 14): `tracing` records the request
        # lifecycle as span ledger events (admit → queue → resolve →
        # batch/dispatch → decode) joined across processes via the
        # traceparent header; `slo` evaluates DEFAULT_SLOS into
        # slo_report events at close. Both OFF by default — the off path
        # is pinned bit-exact with zero added dispatches.
        tracing: bool = False,
        slo: bool = False,
        # incident plane (ISSUE 18 — obs/incident.py): a bundle-root dir
        # string (the engine builds its own IncidentManager with crash
        # hooks) or a shared IncidentManager instance (an in-process
        # fleet debounces across replicas). None = off, bit-exact.
        incidents: Any = None,
    ):
        from videop2p_tpu.cli.common import make_run_ledger

        self.out_dir = out_dir
        os.makedirs(out_dir, exist_ok=True)
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self.batch_dispatch = batch_dispatch
        self.keep_videos = bool(keep_videos)
        self.max_queue = max(int(max_queue), 1)
        self.default_deadline_s = default_deadline_s
        self.dispatch_timeout_s = dispatch_timeout_s
        self.retry = RetryPolicy(max_retries=max_retries, base_s=retry_base_s,
                                 cap_s=retry_cap_s)
        self.breaker = CircuitBreaker(threshold=breaker_threshold,
                                      open_s=breaker_open_s,
                                      on_transition=self._on_breaker)
        self.faults = faults if faults is not None else FaultPlan.from_env()
        self.tenants: Dict[str, TenantConfig] = (
            parse_tenants(tenants) if isinstance(tenants, str)
            else dict(tenants or {})
        )
        if isinstance(scheduler, Scheduler):
            self.scheduler = scheduler
        else:
            self.scheduler = make_scheduler(
                str(scheduler or "drain"),
                max_batch=self.max_batch, max_wait_s=self.max_wait_s,
                max_batch_wait_s=max_batch_wait_s, order=batch_order,
                tenants=self.tenants,
            )
        self.ledger = make_run_ledger(
            ledger_path or os.path.join(out_dir, "serve_ledger.jsonl"),
            enable=True, latency=True, set_latency_env=False,
            meta={"cli": "serve", "spec": dict(spec.resolved().__dict__),
                  "scheduler": self.scheduler.name,
                  "faults": getattr(self.faults, "spec", None),
                  "tracing": bool(tracing)},
            mesh=spec.mesh,
        )
        # the engine's `tracing` switch governs every span of its ledger,
        # the `program.call` spans of its dispatches included (obs/spans.py
        # `span` writes through the active ledger's tracer)
        self.tracer = self.ledger.tracer = Tracer(self.ledger, enabled=tracing)
        self._tracing = self.tracer.enabled
        self._slo = bool(slo)
        # cost & capacity plane (ISSUE 19 — obs/cost.py): static program
        # costs stream in through the ledger's analysis observer as
        # programs compile; the worker prices every successful dispatch
        # by fair share, terminal records carry the per-request cost
        # vector, and close() emits the cost_attribution chargeback rows
        self.cost = CostModel()
        self.ledger.analysis_observers.append(self.cost.observe_program)
        # per-rid fresh-inversion attribution, folded into the terminal
        # cost vector by _finish (a failed request's entry just ages out
        # with the engine — its seconds are already in the capacity books)
        self._resolve_costs: Dict[str, Dict[str, Any]] = {}
        # most-recent-wins ring (ISSUE 18 satellite): a long chaos run
        # must keep the LAST 256 fault/breaker entries — the ones an
        # incident needs — not the first 256. deque(maxlen=...) evicts
        # the oldest on append; consumers iterate it like the old list.
        self.fault_log: Deque[Dict[str, Any]] = deque(maxlen=_FAULT_LOG_MAX)
        self.counters: Dict[str, int] = {
            "shed": 0, "rejected_unavailable": 0, "retries": 0,
            "faults_injected": 0, "rehydrations": 0, "fresh_inversions": 0,
        }
        # per-tenant QoS accounting (serve_health "tenants" / /metrics)
        self.tenant_counters: Dict[str, Dict[str, int]] = {}
        self._counter_lock = threading.Lock()
        self._seq = 0
        self._qw_sum = 0.0
        self._qw_count = 0
        if self.faults is not None:
            self.faults.on_inject = self._fault_event
        self.programs = programs if programs is not None else ProgramSet(spec)
        self.spec = self.programs.spec
        # per-request `steps` is admitted only against this set — unknown
        # step geometry is a 400 at submit, never a cold compile mid-serve.
        # A shared (already-warm) ProgramSet — replicas in one process —
        # hands its warmed buckets straight to this engine.
        self.warm_steps = {self.spec.steps}
        # same admission contract for reuse schedules: only warmed scan
        # bodies are served (the spec default is warmed by ProgramSet.warm)
        self.warm_reuse = {self.spec.reuse_schedule}
        # student buckets start EMPTY — there is no implicit student
        # geometry; only explicitly warmed (student_ckpt + student_steps)
        # buckets are admitted
        self.warm_student: set = set()
        if self.programs.warmed:
            self.warm_steps.update(self.programs.warmed.get("steps", []))
            self.warm_reuse.update(self.programs.warmed.get("reuse", []))
            self.warm_student.update(self.programs.warmed.get("student", []))
        self.store = InversionStore(store_budget_bytes, persist_dir=persist_dir,
                                    faults=self.faults)
        self._spec_fp = self.spec.fingerprint()
        # incident plane (ISSUE 18): tee this ledger into the manager's
        # flight ring, register this engine as a /healthz+/metrics
        # snapshot target and its reservoirs as the trace-id exemplar
        # source. A shared manager (in-process fleet) is used as-is and
        # NOT closed by this engine; a dir string builds an owned one.
        self.incidents = None
        self._own_incidents = False
        if incidents is not None:
            from videop2p_tpu.obs.incident import IncidentManager

            if isinstance(incidents, IncidentManager):
                self.incidents = incidents
            else:
                self.incidents = IncidentManager(str(incidents),
                                                 crash_hooks=True)
                self._own_incidents = True
            self.incidents.attach_ledger(self.ledger)
            self.incidents.note_fingerprint(
                f"engine:{self.ledger.run_id}", self._spec_fp)
            self.incidents.register_target(
                f"engine:{self.ledger.run_id}",
                lambda: {"healthz": self.health_record(),
                         "metrics": self.metrics()})
            self.incidents.register_exemplars(
                self.ledger.execute_timing_summary)
        self._requests: Dict[str, Dict[str, Any]] = {}
        self._videos: Dict[str, np.ndarray] = {}
        self._req_lock = threading.Lock()
        self._inflight = 0
        self._queue: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._closed = False
        self._drain_until = float("inf")
        self.started = time.perf_counter()
        self._worker = threading.Thread(
            target=self._worker_loop, name="edit-engine", daemon=True
        )
        self._worker.start()

    # ---- public API ------------------------------------------------------

    def warm(self, prompts: Sequence[str] = ("a video", "an edited video"),
             *, controller_kwargs: Optional[Dict] = None,
             batch_sizes: Sequence[int] = (2,),
             step_buckets: Sequence[int] = (),
             reuse_schedules: Sequence[str] = (),
             student_steps: Sequence[int] = ()) -> Dict[str, Any]:
        """Compile the request path on zeros (see
        :meth:`videop2p_tpu.serve.programs.ProgramSet.warm`); the summary
        lands in the ledger and ``/healthz``. ``step_buckets`` additionally
        warms few-step timestep-subset edit variants — the step counts
        per-request ``steps`` may then ask for; ``reuse_schedules`` warms
        cross-step deep-feature reuse scan bodies the same way for
        per-request ``reuse_schedule``; ``student_steps`` warms the
        consistency-distilled student's buckets (requires the spec's
        ``student_ckpt``) for per-request ``student=True``."""
        info = self.programs.warm(
            prompts, controller_kwargs=controller_kwargs,
            batch_sizes=batch_sizes, dispatch=self.batch_dispatch,
            step_buckets=step_buckets, reuse_schedules=reuse_schedules,
            student_steps=student_steps,
        )
        self.warm_steps.update(info.get("steps", []))
        self.warm_reuse.update(info.get("reuse", []))
        self.warm_student.update(info.get("student", []))
        self.ledger.event("serve_warm", **info)
        return info

    def submit(self, request: EditRequest, *,
               traceparent: Optional[str] = None) -> str:
        """Enqueue one request; returns its id immediately.

        ``traceparent`` (tracing on) joins this request to an inbound
        distributed trace — the HTTP layer passes the header through; a
        missing/malformed value starts a fresh trace. With tracing off it
        is ignored entirely.

        Fast-fail surfaces (each one machine-readable at the HTTP layer):
        a closed engine or an OPEN circuit breaker raises
        :class:`EngineUnavailable` (503, ``Retry-After`` = the breaker's
        remaining open window); a full admit queue raises
        :class:`QueueFull` (429 with the depth); a per-request ``steps``
        outside the warmed buckets raises ``ValueError`` (400) listing the
        warm list — unknown step geometry must not silently compile cold
        mid-serve."""
        tenant = request.tenant or "default"
        if self._closed:
            raise EngineUnavailable("engine is closed")
        if not self.breaker.allow():
            self._count("rejected_unavailable")
            self._tcount(tenant, "rejected")
            raise EngineUnavailable(
                f"circuit breaker open after "
                f"{self.breaker.consecutive_failures} consecutive dispatch "
                "failures — backend presumed unhealthy",
                retry_after_s=self.breaker.retry_after_s(),
            )
        request.validate()
        steps = int(request.steps) if request.steps else self.spec.steps
        if request.student:
            # student admission replaces the teacher step-bucket check: a
            # student bucket is its OWN warmed geometry (distilled params +
            # head program), independent of the teacher buckets
            if self.programs.student_head is None:
                raise ValueError(
                    "student=True but this program set has no student "
                    "checkpoint — build the set with --student_ckpt "
                    "(ProgramSpec.student_ckpt) and warm student buckets "
                    "(EditEngine.warm(student_steps=...) / cli.serve "
                    "--student_buckets)"
                )
            if steps not in self.warm_student:
                raise ValueError(
                    f"steps={steps} is not a warmed student bucket (warmed "
                    f"student: {sorted(self.warm_student)}) — a cold student "
                    "program would compile mid-serve; warm it first "
                    "(EditEngine.warm(student_steps=...) / cli.serve "
                    "--student_buckets)"
                )
        elif steps not in self.warm_steps:
            raise ValueError(
                f"steps={steps} is not a warmed step bucket (warmed: "
                f"{sorted(self.warm_steps)}) — cold step geometry would "
                "compile mid-serve; warm it first "
                "(EditEngine.warm(step_buckets=...) / cli.serve --step_buckets)"
            )
        if (request.quant_mode is not None
                and request.quant_mode != self.spec.quant_mode):
            raise ValueError(
                f"quant_mode={request.quant_mode!r} does not match this "
                f"program set (serving quant_mode={self.spec.quant_mode!r}) — "
                "weights are quantized at set build, not per request; route "
                "to a set built with that mode (cli.serve --quant_mode)"
            )
        from videop2p_tpu.pipelines.reuse import validate_reuse_schedule

        reuse = (request.reuse_schedule if request.reuse_schedule is not None
                 else self.spec.reuse_schedule)
        # grammar first (a malformed schedule gets the grammar error, not
        # the warm-list one), against the resolved step count
        reuse = validate_reuse_schedule(reuse, steps)
        if reuse not in self.warm_reuse:
            raise ValueError(
                f"reuse_schedule={reuse!r} is not a warmed schedule (warmed: "
                f"{sorted(self.warm_reuse)}) — a cold reuse scan body would "
                "compile mid-serve; warm it first "
                "(EditEngine.warm(reuse_schedules=...) / cli.serve "
                "--reuse_buckets)"
            )
        rid = uuid.uuid4().hex[:12]
        now = time.perf_counter()
        # deadline budget resolution: the request's own > the tenant's
        # TenantConfig default > the engine default
        deadline_s = request.deadline_s
        if deadline_s is None:
            tcfg = self.tenants.get(tenant)
            deadline_s = tcfg.deadline_s if tcfg is not None else None
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        rec = {
            "id": rid,
            "status": "queued",
            "submitted_s": now,
            "deadline_s": deadline_s,
            "deadline_at": (now + float(deadline_s)
                            if deadline_s is not None else None),
            "tenant": tenant,
            "request": {k: v for k, v in request.to_dict().items()
                        if k != "frames"},
            "compile_events_before": len(self.ledger.compile_seconds),
        }
        if self._tracing:
            # the request's root-span identity: join the inbound trace
            # (router proxy / client) or start fresh. `_wall_ns` anchors
            # every retroactive span of this request to the wall clock.
            parsed = parse_traceparent(traceparent)
            trace_id, parent = parsed if parsed else (make_trace_id(), None)
            rec["trace_id"] = trace_id
            rec["span_id"] = make_span_id()
            rec["_span_parent"] = parent
            rec["_wall_ns"] = time.time_ns()
        with self._req_lock:
            if self._inflight >= self.max_queue:
                depth = self._inflight
            else:
                depth = None
                self._seq += 1
                rec["seq"] = self._seq
                self._requests[rid] = rec
                self._inflight += 1
        if depth is not None:
            self._count("shed")
            self._tcount(tenant, "shed")
            raise QueueFull(depth, self.max_queue)
        self._tcount(tenant, "submitted")
        self._queue.put((rid, request))
        return rid

    def poll(self, rid: str) -> Dict[str, Any]:
        """JSON-safe snapshot of one request's record."""
        with self._req_lock:
            rec = self._requests.get(rid)
            if rec is None:
                raise KeyError(f"unknown request id {rid!r}")
            return json.loads(json.dumps(rec, default=str))

    def result(self, rid: str, *, wait_s: float = 0.0,
               poll_interval_s: float = 0.02) -> Dict[str, Any]:
        """The record once terminal; with ``wait_s`` blocks up to that long."""
        deadline = time.perf_counter() + max(float(wait_s), 0.0)
        while True:
            rec = self.poll(rid)
            if rec["status"] in TERMINAL_STATUSES:
                return rec
            if time.perf_counter() >= deadline:
                return rec
            time.sleep(poll_interval_s)

    def videos(self, rid: str) -> Optional[np.ndarray]:
        """The decoded (P, F, H, W, 3) [0,1] array for in-process callers
        (kept only with ``keep_videos=True``)."""
        return self._videos.get(rid)

    def take_videos(self, rid: str) -> Optional[np.ndarray]:
        """Pop (and return) one request's kept videos — the streaming
        driver's memory-flat harvest: a long job holds at most its
        in-flight windows resident instead of accumulating every decoded
        window for the life of the engine."""
        return self._videos.pop(rid, None)

    def metrics(self) -> Dict[str, Any]:
        """The live SLO record ``/metrics`` serves: per-program and
        per-phase latency distributions straight from the ledger's
        reservoirs, compile-vs-execute split, store hit rates, request
        counts, queue-depth / in-flight gauges, the breaker snapshot,
        resilience counters and per-device HBM."""
        with self._req_lock:
            by_status: Dict[str, int] = {}
            for rec in self._requests.values():
                by_status[rec["status"]] = by_status.get(rec["status"], 0) + 1
            in_flight = self._inflight
        timing = self.ledger.execute_timing_summary()
        request_latency = timing.get("serve_request_e2e")
        uptime_s = time.perf_counter() - self.started
        return {
            "uptime_s": round(uptime_s, 3),
            "spec_fingerprint": self._spec_fp,
            "warm": self.programs.warmed,
            "requests": by_status,
            "queue_depth": self._queue.qsize(),
            "in_flight": in_flight,
            "max_queue": self.max_queue,
            "scheduler": self.scheduler.snapshot(),
            "tenants": self._tenant_records(),
            "breaker": self.breaker.snapshot(),
            "counters": dict(self.counters),
            "store": self.store.stats(),
            "compile": {
                "events": len(self.ledger.compile_seconds),
                "total_s": round(sum(self.ledger.compile_seconds), 4),
            },
            "request_latency": request_latency,
            "programs": timing,
            # capacity accounting (ISSUE 19): busy/idle fraction, padding
            # waste, slot occupancy, cost-per-request — the collector
            # meters these into utilization/headroom series and priced
            # scale_advice (JSON and Prometheus expose the same record)
            "capacity": self.cost.capacity(uptime_s),
            "devices": self._device_memory(),
        }

    def _tenant_records(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant QoS accounting (``SERVE_TENANT_FIELDS``): terminal
        outcomes plus error/shed rates per tenant lane."""
        with self._counter_lock:
            counters = {t: dict(c) for t, c in self.tenant_counters.items()}
        # measured per-tenant attribution (ISSUE 19): cumulative device-
        # seconds and cache savings join the QoS counters — the fleet
        # collector meters these as counter series, so signals' demand
        # lanes report MEASURED device-seconds, not a scrape estimate
        costs = self.cost.tenant_costs()
        out: Dict[str, Dict[str, Any]] = {}
        for t, c in counters.items():
            done = c.get("done", 0)
            errors = c.get("errors", 0)
            deadline_exceeded = c.get("deadline_exceeded", 0)
            finished = (done + errors + deadline_exceeded
                        + c.get("engine_closed", 0))
            attempts = c.get("submitted", 0) + c.get("shed", 0) + c.get("rejected", 0)
            tcost = costs.get(t, {})
            out[t] = {
                **c,
                "error_rate": (round((errors + deadline_exceeded) / finished, 4)
                               if finished else 0.0),
                "shed_rate": (round((c.get("shed", 0) + c.get("rejected", 0))
                                    / attempts, 4) if attempts else 0.0),
                "device_seconds": round(tcost.get("device_seconds", 0.0), 6),
                "saved_device_seconds": round(
                    tcost.get("saved_device_seconds", 0.0), 6),
            }
        return out

    def health_record(self) -> Dict[str, Any]:
        """The ``serve_health`` reliability summary (obs/history.py's
        ``reliability`` section; gated by ``FAULT_RULES``): request
        outcomes by terminal status, error/shed rates, breaker trips,
        the injection/recovery counters, the scheduling policy with its
        mean queue wait, and the per-tenant QoS sub-records."""
        with self._req_lock:
            by_status: Dict[str, int] = {}
            for rec in self._requests.values():
                by_status[rec["status"]] = by_status.get(rec["status"], 0) + 1
        admitted = sum(by_status.values())
        done = by_status.get("done", 0)
        errors = by_status.get("error", 0)
        deadline_exceeded = by_status.get("deadline_exceeded", 0)
        engine_closed = by_status.get("engine_closed", 0)
        shed = self.counters["shed"]
        rejected = self.counters["rejected_unavailable"]
        attempts = admitted + shed + rejected
        capacity = self.cost.capacity(time.perf_counter() - self.started)
        return {
            "requests": admitted,
            "done": done,
            "errors": errors,
            "deadline_exceeded": deadline_exceeded,
            "engine_closed": engine_closed,
            "shed": shed,
            "rejected_unavailable": rejected,
            "error_rate": (round((errors + deadline_exceeded) / admitted, 4)
                           if admitted else 0.0),
            "shed_rate": (round((shed + rejected) / attempts, 4)
                          if attempts else 0.0),
            "breaker_trips": self.breaker.trips,
            "retries": self.counters["retries"],
            "faults_injected": self.counters["faults_injected"],
            "rehydrations": self.counters["rehydrations"],
            "fresh_inversions": self.counters["fresh_inversions"],
            "store_corrupt": self.store.disk_corrupt,
            "scheduler": self.scheduler.name,
            "queue_wait_mean_s": (round(self._qw_sum / self._qw_count, 4)
                                  if self._qw_count else 0.0),
            "busy_fraction": capacity["busy_fraction"],
            "padding_waste": capacity["padding_waste"],
            "tenants": self._tenant_records(),
        }

    def cost_records(self) -> List[Dict[str, Any]]:
        """The live ``cost_attribution`` rows (obs/cost.py,
        ``COST_ATTRIBUTION_FIELDS``): the engine-scope capacity roll-up
        plus the per-tenant / per-program chargeback aggregates — what
        close() emits, readable any time (the loadgen lands them into
        its own ledger the way it lands ``serve_health``)."""
        return self.cost.attribution_records(
            time.perf_counter() - self.started)

    def close(self, *, drain_s: float = 0.0) -> None:
        """Stop admitting, stop the worker, and FAIL every still-pending
        request with terminal status ``engine_closed`` — nothing is ever
        left ``queued``/``resolving``/``running`` forever. With
        ``drain_s`` > 0, first give queued work that long to finish (the
        SIGTERM graceful-drain window in ``cli/serve.py``); the in-flight
        dispatch always completes either way. Writes the ``serve_health``
        summary, flushes execute timing and closes the ledger."""
        if self._closed:
            return
        self._closed = True
        self._drain_until = time.perf_counter() + max(float(drain_s), 0.0)
        if drain_s > 0:
            while time.perf_counter() < self._drain_until:
                with self._req_lock:
                    if self._inflight == 0:
                        break
                time.sleep(0.02)
        self._queue.put(None)
        self._worker.join(timeout=60.0)
        # drain the queue (items the worker never took) and terminalize
        # every non-terminal record — incl. any submit that raced close()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        with self._req_lock:
            pending = [rid for rid, rec in self._requests.items()
                       if rec["status"] not in TERMINAL_STATUSES]
        for rid in pending:
            self._fail_status(rid, "engine_closed",
                              "engine closed before completion")
        health = self.health_record()
        if self._slo:
            # evaluate the declarative objectives over the LIVE summaries
            # (obs/slo.py) — one slo_report event per objective, before
            # the health summary so both land in the same run record
            try:
                from videop2p_tpu.obs.slo import (
                    emit_slo_reports,
                    record_from_summaries,
                )

                emit_slo_reports(self.ledger, record_from_summaries(
                    health=health,
                    timing=self.ledger.execute_timing_summary(),
                ))
            except Exception:  # noqa: BLE001 — obs never blocks shutdown
                pass
        # the chargeback ledger (ISSUE 19): one engine-scope capacity
        # roll-up (the conservation invariant on the books: attributed +
        # padding = busy, idle explicit) plus one row per tenant and per
        # program — before serve_health so one run record carries both
        for row in self.cost_records():
            self.ledger.event("cost_attribution", label="serve", **row)
        self.ledger.event("serve_health", **health)
        self.ledger.event("serve_shutdown", requests=len(self._requests))
        if self.incidents is not None and self._own_incidents:
            try:
                self.incidents.close()  # restores the crash hooks
            except Exception:  # noqa: BLE001 — obs never blocks shutdown
                pass
        self.ledger.close()

    def __enter__(self) -> "EditEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- fault / breaker bookkeeping ------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        with self._counter_lock:
            self.counters[name] = self.counters.get(name, 0) + n

    _TENANT_COUNTER_KEYS = ("submitted", "done", "errors",
                            "deadline_exceeded", "engine_closed", "shed",
                            "rejected")

    def _tcount(self, tenant: str, name: str, n: int = 1) -> None:
        with self._counter_lock:
            d = self.tenant_counters.setdefault(
                tenant, {k: 0 for k in self._TENANT_COUNTER_KEYS}
            )
            d[name] = d.get(name, 0) + n

    def _fault_event(self, kind: str, **fields: Any) -> None:
        """One fault observation (injected via the FaultPlan's on_inject
        callback, or engine-classified): ledger ``fault`` event + the
        bounded in-memory log + the injection counter."""
        detail = ", ".join(f"{k}={v}" for k, v in fields.items()) or kind
        if kind in ("dispatch_fail", "backend_unavailable", "hang",
                    "store_corrupt"):
            self._count("faults_injected")
        entry = {"event": "fault", "kind": kind, "detail": detail}
        self.fault_log.append(entry)  # ring: oldest evicts, tail survives
        self.ledger.fault(kind, detail=detail)

    def _on_breaker(self, state_from: str, state_to: str, *,
                    consecutive_failures: int, trips: int) -> None:
        entry = {"event": "breaker", "state_from": state_from,
                 "state_to": state_to,
                 "consecutive_failures": consecutive_failures, "trips": trips}
        self.fault_log.append(entry)  # ring: oldest evicts, tail survives
        self.ledger.breaker(state_from, state_to,
                            consecutive_failures=consecutive_failures,
                            trips=trips)
        if state_to == "open" and self.incidents is not None:
            # the breaker declaring the backend unhealthy IS the incident
            # — capture the flight ring while the evidence is still hot
            self.incidents.trigger(
                "breaker_open",
                detail=(f"{state_from}->open after {consecutive_failures} "
                        f"consecutive dispatch failures (trip {trips})"),
                consecutive_failures=consecutive_failures, trips=trips)

    # ---- worker ----------------------------------------------------------

    def _worker_loop(self) -> None:
        """The scheduling loop (ISSUE 11): the pluggable policy picks the
        admit window (``collect``), the worker resolves what it pulled,
        and the policy forms dispatch batches (``next_plan``). Preemptive
        policies (continuous, fair) return to ``collect`` after EVERY
        dispatch — that is iteration-level admission: a compatible request
        arriving mid-dispatch joins the next batch. The drain policy keeps
        the classic plan boundary (every planned batch dispatches before
        the next window opens) and is pinned bit-exact vs the
        pre-scheduler engine."""
        sched = self.scheduler
        while True:
            raw = sched.collect(self)
            if raw is None:
                break
            prepared = []
            for rid, request in raw:
                p = self._resolve(rid, request)
                if p is not None:
                    prepared.append(p)
            if prepared:
                sched.add(prepared)
            while True:
                plan = sched.next_plan(time.perf_counter(),
                                       queue_empty=self._queue.empty())
                if plan is None:
                    break
                try:
                    self._dispatch(plan)
                except Exception as e:  # noqa: BLE001 — the worker must outlive ANY batch
                    for p in plan.items:
                        self._fail(p.rid, f"dispatch failed unexpectedly: {e}",
                                   time.perf_counter())
                if sched.preemptive:
                    break
        self._done.set()

    def _collect_window(self, max_items: int, window_s: float, *,
                        first_timeout_s: float = 0.2,
                        oldest_budget_s: Optional[float] = None,
                        greedy: bool = False):
        """One admit window (the schedulers parameterize it): block up to
        ``first_timeout_s`` for the first request, then keep draining
        compatible-or-not requests until ``max_items`` are in hand or
        ``window_s`` elapses (grouping happens after resolve — an
        incompatible request simply lands in its own batch).
        ``oldest_budget_s`` additionally caps the window by the FIRST
        request's total time-in-queue since submit (the drain policy's
        ``max_batch_wait_s`` knob); ``greedy`` keeps taking
        already-queued requests after the window closes without blocking
        (the continuous/fair policies' instant drain). A closed engine
        past its drain window stops collecting — close() fails whatever
        is left."""
        if self._closed and time.perf_counter() >= self._drain_until:
            return None
        try:
            first = self._queue.get(timeout=first_timeout_s)
        except queue.Empty:
            return []
        if first is None:
            return None
        items = [first]
        deadline = time.perf_counter() + window_s
        if oldest_budget_s is not None:
            with self._req_lock:
                rec = self._requests.get(first[0])
                submitted = rec.get("submitted_s") if rec else None
            if submitted is not None:
                deadline = min(deadline, submitted + float(oldest_budget_s))
        while len(items) < max_items:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                if not greedy:
                    break
                try:
                    nxt = self._queue.get_nowait()
                except queue.Empty:
                    break
            else:
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
            if nxt is None:
                self._queue.put(None)  # re-post the sentinel for the outer loop
                break
            items.append(nxt)
        return items

    def _update(self, rid: str, **fields) -> Dict[str, Any]:
        with self._req_lock:
            rec = self._requests[rid]
            rec.update(fields)
            return rec

    def _deadline_expired(self, rid: str) -> bool:
        with self._req_lock:
            rec = self._requests.get(rid)
            at = rec.get("deadline_at") if rec else None
        return at is not None and time.perf_counter() > at

    def _deadline_remaining(self, rid: str) -> Optional[float]:
        with self._req_lock:
            rec = self._requests.get(rid)
            at = rec.get("deadline_at") if rec else None
        return None if at is None else at - time.perf_counter()

    def _store_key(self, request: EditRequest, ctx) -> str:
        """Content-addressed inversion-product identity: the program-set
        fingerprint (checkpoint content + geometry + steps) x the clip
        content x the source prompt x the capture plan the controller
        implies. Anything that changes the products changes the key."""
        import hashlib

        from videop2p_tpu.pipelines.cached import capture_windows
        from videop2p_tpu.utils.inv_cache import (
            content_fingerprint,
            inversion_cache_key,
        )

        if request.frames is not None:
            clip = hashlib.sha256(
                np.ascontiguousarray(request.frames).tobytes()
            ).hexdigest()[:16]
        else:
            clip = content_fingerprint(os.path.abspath(request.image_path))
        cross_len, self_window = capture_windows(ctx, self.spec.steps)
        return inversion_cache_key(
            spec=self._spec_fp, clip=clip, prompt=request.prompt,
            seed=request.seed, cross_len=cross_len, self_window=self_window,
            capture_blend=ctx.blend is not None,
        )

    def _resolve(self, rid: str, request: EditRequest) -> Optional[_Prepared]:
        """Admit one request: controller, prompt encodings, store lookup
        (resident → disk-rehydration → fresh), and on a full miss the
        once-per-clip encode + capture-inversion."""
        t0 = time.perf_counter()
        if self._deadline_expired(rid):
            self._fail_status(rid, "deadline_exceeded",
                              "deadline expired before resolve")
            return None
        with self._req_lock:
            rec0 = self._requests.get(rid) or {}
            submitted = rec0.get("submitted_s")
            seq = rec0.get("seq", 0)
            deadline_at = rec0.get("deadline_at")
            tenant = rec0.get("tenant", "default")
            tid = rec0.get("trace_id") if self._tracing else None
            root_span = rec0.get("span_id")
            wall0 = rec0.get("_wall_ns")
        # queue wait: submit → the worker picking the request up. The
        # continuous-vs-drain acceptance compares this reservoir's mean
        # across scheduling policies on the same trace.
        queue_wait_s = max(t0 - submitted, 0.0) if submitted else 0.0
        self.ledger.record_execute("serve_queue_wait", queue_wait_s,
                                   queue_wait_s, tid)
        with self._counter_lock:
            self._qw_sum += queue_wait_s
            self._qw_count += 1
        self._update(rid, status="resolving",
                     queue_wait_s=round(queue_wait_s, 4))
        if tid:
            # the queue segment spans submit → here; its start IS the
            # request's wall anchor
            self.tracer.emit(
                "serve.queue", trace_id=tid, span_id=make_span_id(),
                parent_id=root_span, wall_ns=wall0,
                duration_s=queue_wait_s, rid=rid,
            )
        try:
            # the resolve segment, worker pickup → prepared arguments
            # (inert with tracing off: Tracer.enabled gates every span)
            with span("serve.resolve", tracer=self.tracer, trace_id=tid,
                      parent_id=root_span, rid=rid) as resolve_span:
                ps = self.programs
                steps = int(request.steps) if request.steps else self.spec.steps
                controller_kwargs = dict(
                    is_word_swap=request.is_word_swap,
                    cross_replace_steps=request.cross_replace_steps,
                    self_replace_steps=request.self_replace_steps,
                    blend_word=request.blend_word,
                    eq_params=request.eq_params,
                )
                # the BASE-steps controller keys the store/capture (inversions
                # are always captured at the base grid); a few-step request
                # additionally builds its own subset-space controller below
                ctx = ps.controller(list(request.prompts), **controller_kwargs)
                cond_all = ps.encode_prompts(list(request.prompts))
                uncond = ps.encode_prompts([""])[0]
                key = self._store_key(request, ctx)
                products = self.store.get(key)
                source = "memory" if products is not None else None
                _, ik = jax.random.split(jax.random.key(request.seed))
                if products is None:
                    # lazy crash-recovery rehydration: the persisted trajectory's
                    # leading entry IS the encoded source latents, so the warm
                    # inversion program rebuilds bit-identical capture products
                    # from it — no frame IO, no VAE encode, no cold compile,
                    # and no NEW inversion-from-frames on the books
                    traj_np = self.store.load_disk(key)
                    if traj_np is not None and traj_np.shape[0] == self.spec.steps + 1:
                        anchor = jnp.asarray(traj_np[0])
                        _, cached = ps.invert_capture(
                            anchor, ps.encode_prompts([request.prompt]), ctx, ik
                        )[:2]
                        products = (cached, anchor)
                        source = "disk"
                        self._count("rehydrations")
                        # resident again; already on disk — no re-persist
                        self.store.put(key, products)
                if products is None:
                    if request.frames is not None:
                        frames = np.asarray(request.frames)
                    else:
                        from videop2p_tpu.data import load_frame_sequence

                        frames = load_frame_sequence(
                            request.image_path, size=self.spec.width,
                            num_frames=self.spec.video_len,
                        )
                    latents = ps.encode(
                        ps.frames_to_video(frames), jax.random.key(request.seed)
                    )
                    traj, cached = ps.invert_capture(
                        latents, ps.encode_prompts([request.prompt]), ctx, ik
                    )[:2]
                    products = (cached, latents)
                    source = "fresh"
                    self._count("fresh_inversions")
                    self.store.put(
                        key, products,
                        trajectory=(np.asarray(jax.device_get(traj))
                                    if self.store.persist_dir else None),
                        meta={"image_path": request.image_path,
                              "prompt": request.prompt,
                              "steps": self.spec.steps,
                              "width": self.spec.width,
                              "video_len": self.spec.video_len},
                    )
                if source == "fresh":
                    # the measured price one store hit avoids: this clip's
                    # encode + capture-inversion resolve seconds (slightly
                    # over the pure inversion — the controller/prompt-encode
                    # share is common to hits too, and small next to it).
                    # The same seconds are PRICED to this request as a
                    # singleton serve_invert attribution: a cold request
                    # carries its inversion in the cost vector, so a store
                    # hit's attributed cost is measurably lower — and the
                    # inversion seconds stay inside the conservation books
                    # (busy += attributed, no padding).
                    inv_s = time.perf_counter() - t0
                    self.cost.note_fresh_inversion(inv_s)
                    self._resolve_costs[rid] = self.cost.price_dispatch(
                        inv_s, real=1, padded=1, program="serve_invert")
                cached, anchor = products
                ctx_edit = ctx
                if steps != self.spec.steps:
                    from videop2p_tpu.pipelines.cached import check_subset_windows

                    ctx_edit = ps.controller(
                        list(request.prompts), steps=steps, **controller_kwargs
                    )
                    _, positions = ps.step_plan(steps)
                    check_subset_windows(ctx_edit, cached, positions, steps)
                args = (cached, cond_all, uncond, ctx_edit, anchor)
                dt = time.perf_counter() - t0
                self.ledger.record_execute("serve_resolve", dt, dt, tid)
                self._update(rid, store_hit=source in ("memory", "disk"),
                             store_source=source, store_key=key, steps=steps,
                             resolve_s=round(dt, 4))
                resolve_span.set(store_source=source, steps=steps)
            reuse = (request.reuse_schedule
                     if request.reuse_schedule is not None
                     else self.spec.reuse_schedule)
            student = bool(request.student)
            return _Prepared(
                rid=rid, args=args, steps=steps, reuse=reuse,
                student=student,
                compat=compat_key(args, extra=(
                    self._spec_fp, steps, self.spec.guidance_scale,
                    self.batch_dispatch, reuse, student,
                )),
                seq=seq, arrival_s=t0, deadline_at=deadline_at,
                tenant=tenant,
            )
        except Exception as e:  # noqa: BLE001 — one bad request must not kill the engine
            self._fail(rid, f"resolve failed: {e}", t0)
            return None

    # ---- dispatch: watchdog + retry + breaker ----------------------------

    def _device_dispatch(self, plan) -> List[Tuple[Any, Any]]:
        """The batch's device math (singleton or stacked), blocked until
        ready. The fault seam fires first — inside whatever watchdog
        bounds this call, so an injected hang is bounded exactly like a
        real wedge."""
        if self.faults is not None:
            self.faults.on_dispatch()
        ps = self.programs
        # compat keys carry the step count, reuse schedule and student
        # flag, so a plan is homogeneous in all three
        steps = plan.items[0].steps
        reuse = plan.items[0].reuse
        student = plan.items[0].student
        if plan.padded_size == 1:
            videos, src_err = ps.edit_decode(*plan.items[0].args, steps=steps,
                                             reuse=reuse, student=student)
            outs = [(videos, src_err)]
        else:
            stacked = stack_items(
                [p.args for p in plan.items], plan.padded_size
            )
            videos_b, src_err_b = ps.edit_decode_batch(
                stacked, plan.padded_size, dispatch=self.batch_dispatch,
                steps=steps, reuse=reuse, student=student,
            )
            outs = unstack_outputs((videos_b, src_err_b), len(plan.items))
        jax.block_until_ready([o[0] for o in outs])
        return outs

    def _watchdog_dispatch(self, plan, budget_s: Optional[float]):
        """Bounded block-until-ready: run the device dispatch in a watchdog
        thread and give it ``budget_s``; past the budget the stuck thread
        is ABANDONED (daemon — a wedged device call cannot be cancelled,
        only orphaned) and :class:`DeadlineExceeded` is raised so the
        worker fails the batch and keeps serving. ``budget_s`` None runs
        inline (no watchdog overhead when nothing bounds the dispatch)."""
        if budget_s is None:
            return self._device_dispatch(plan)
        if budget_s <= 0:
            raise DeadlineExceeded("dispatch budget already expired")
        result: Dict[str, Any] = {}
        done = threading.Event()

        def runner():
            try:
                result["out"] = self._device_dispatch(plan)
            except BaseException as e:  # noqa: BLE001 — carried to the worker
                result["exc"] = e
            done.set()

        t = threading.Thread(target=runner, daemon=True,
                             name="edit-engine-dispatch")
        t.start()
        if not done.wait(timeout=budget_s):
            self._fault_event("watchdog_timeout",
                              budget_s=round(budget_s, 3))
            raise DeadlineExceeded(
                f"dispatch exceeded its {budget_s:.3f}s budget "
                "(watchdog abandoned the stuck dispatch)"
            )
        if "exc" in result:
            raise result["exc"]
        return result["out"]

    def _dispatch(self, plan) -> None:
        """One planned batch through the resilience pipeline: deadline
        expiry → bounded dispatch → deterministic retry on transient
        failure → breaker accounting. A failed batch fails only its own
        requests; the worker survives everything."""
        attempt = 0
        failed: set = set()
        while True:
            # expire items whose deadline passed (initial or burned by
            # earlier attempts/backoff); the remaining ones still dispatch
            # through the ORIGINAL plan (their lanes just go unread)
            live = []
            for p in plan.items:
                if p.rid in failed:
                    continue
                if self._deadline_expired(p.rid):
                    failed.add(p.rid)
                    self._fail_status(p.rid, "deadline_exceeded",
                                      "deadline expired before dispatch")
                    continue
                live.append(p)
            if not live:
                return
            budgets = [self.dispatch_timeout_s]
            budgets += [self._deadline_remaining(p.rid) for p in live]
            budgets = [b for b in budgets if b is not None]
            budget = min(budgets) if budgets else None
            t0 = time.perf_counter()
            # per-dispatch occupancy (ISSUE 19 satellite): how many of
            # this dispatch's padded slots carry REAL requests — the
            # padding-waste denominator, threaded into every member's
            # record and the /metrics capacity section
            occupancy = {"real": len(live), "padded": plan.padded_size}
            for p in live:
                self._update(p.rid, status="running",
                             batch_size=len(plan.items),
                             padded_size=plan.padded_size,
                             batch_occupancy=dict(occupancy),
                             dispatch_attempts=attempt + 1)
            try:
                outs = self._watchdog_dispatch(plan, budget)
            except DeadlineExceeded as e:
                # the budget is burned — never retried; the breaker counts
                # it (a wedged device looks exactly like this)
                self.breaker.record_failure()
                if self.incidents is not None:
                    self.incidents.trigger(
                        "deadline_exceeded",
                        detail=f"dispatch watchdog: {e}",
                        batch_size=len(live))
                for p in live:
                    self._fail_status(p.rid, "deadline_exceeded", str(e))
                return
            except Exception as e:  # noqa: BLE001 — classified below
                if (is_transient(e) and attempt < self.retry.max_retries
                        and not self._closed):
                    delay = self.retry.delay_s(attempt)
                    self._count("retries")
                    self._fault_event(
                        "retry", attempt=attempt + 1,
                        backoff_s=round(delay, 4),
                        error=f"{type(e).__name__}: {e}",
                    )
                    time.sleep(delay)
                    attempt += 1
                    continue
                self.breaker.record_failure()
                for p in live:
                    self._fail(p.rid, f"dispatch failed: {e}", t0)
                return
            # success: the breaker's half-open probe (or plain traffic)
            self.breaker.record_success()
            dt = time.perf_counter() - t0
            tid0 = (self._emit_dispatch_spans(live, t0, dt)
                    if self._tracing else None)
            self.ledger.record_execute("serve_dispatch", dt, dt, tid0)
            # fair-share cost attribution (ISSUE 19): the dispatch's
            # blocked seconds split per padded slot — live members each
            # get one slot's share, the pad slots land in the padding-
            # waste line, so attribution + padding sums back to dt
            batched_label, singleton_label = self._cost_labels(plan)
            cost_slot = self.cost.price_dispatch(
                dt, real=len(live), padded=plan.padded_size,
                program=batched_label, singleton=singleton_label,
            )
            for p, (videos, src_err) in zip(plan.items, outs):
                if p.rid in failed:
                    continue
                self._finish(p.rid, np.asarray(jax.device_get(videos)),
                             float(np.asarray(jax.device_get(src_err))), dt,
                             cost_slot=cost_slot)
            return

    def _emit_dispatch_spans(self, live, t0: float,
                             dt: float) -> Optional[str]:
        """The batch's span structure: a span belongs to ONE trace but a
        batch serves many, so one ``serve.batch`` span lands under the
        FIRST member's trace carrying a fresh ``batch_id`` plus the member
        rids, and every member request gets its own ``serve.dispatch``
        child span carrying the same ``batch_id`` as the cross-trace link.
        Returns the first member's trace_id (the dispatch reservoir's
        exemplar)."""
        batch_id = make_span_id()
        members = [p.rid for p in live]
        with self._req_lock:
            recs = {p.rid: dict(self._requests.get(p.rid) or {})
                    for p in live}
        first_tid = None
        for p in live:
            rec = recs.get(p.rid) or {}
            tid = rec.get("trace_id")
            if not tid:
                continue
            wall0, submitted = rec.get("_wall_ns"), rec.get("submitted_s")
            wall = (wall0 + int((t0 - submitted) * 1e9)
                    if wall0 is not None and submitted else None)
            if first_tid is None:
                first_tid = tid
                self.tracer.emit(
                    "serve.batch", trace_id=tid, span_id=batch_id,
                    parent_id=rec.get("span_id"), wall_ns=wall,
                    duration_s=dt, batch_id=batch_id,
                    batch_size=len(live), members=members,
                )
            self.tracer.emit(
                "serve.dispatch", trace_id=tid, span_id=make_span_id(),
                parent_id=rec.get("span_id"), wall_ns=wall, duration_s=dt,
                rid=p.rid, batch_id=batch_id, batch_size=len(live),
            )
        return first_tid

    def _cost_labels(self, plan) -> Tuple[str, str]:
        """The (dispatched, singleton) program labels of one plan — the
        CostModel's static-cost lookup keys, mirroring the label scheme
        :mod:`videop2p_tpu.serve.programs` compiles under (so the join
        lands on the exact analyzed program when it has compiled, and
        falls back to the singleton's per-item statics otherwise)."""
        from videop2p_tpu.pipelines.reuse import reuse_label

        p0 = plan.items[0]
        suffix = "" if p0.steps == self.spec.steps else f"_s{p0.steps}"
        rl = reuse_label(p0.reuse)
        if rl:
            suffix += f"_r{rl}"
        if p0.student:
            suffix += "_stu"
        singleton = f"serve_edit{suffix}"
        if plan.padded_size == 1:
            return singleton, singleton
        batched = (f"serve_edit_b{plan.padded_size}"
                   f"_{self.batch_dispatch}{suffix}")
        return batched, singleton

    def _finish(self, rid: str, videos: np.ndarray, src_err: float,
                dispatch_s: float,
                cost_slot: Optional[Dict[str, Any]] = None) -> None:
        from videop2p_tpu.utils.video_io import save_video_gif

        rec = self.poll(rid)
        req = rec["request"]
        tid = rec.get("trace_id") if self._tracing else None
        # the decode segment: answer identity, canary metrics, the request's
        # directory; the GIF writing that used to hide in it is
        # serve.gif_write (inert with tracing off)
        with span("serve.decode", tracer=self.tracer, trace_id=tid,
                  parent_id=rec.get("span_id"), rid=rid):
            if self.faults is not None and self.faults.wrong:
                # silent wrong-answer seam (wrong:PAT): deterministically
                # perturb the tensor — the replica stays self-consistent
                # (same bytes every replay, 200s, healthy /healthz) but its
                # content hash diverges from the fleet's, which only the
                # cross-replica answer audit (obs/probe.py) catches
                if self.faults.wrongs(rec.get("store_key") or rid):
                    videos = np.ascontiguousarray(np.asarray(videos)[..., ::-1])
            # stable answer identity: byte hash of the full video tensor —
            # the determinism probe and the bit-exactness tests compare
            # THIS, not re-hashed GIF artifacts
            content_sha256 = hashlib.sha256(
                np.ascontiguousarray(np.asarray(videos)).tobytes()).hexdigest()
            quality = None
            if rec.get("tenant") == PROBE_TENANT:
                # golden-quality canary metrics — computed ONLY for the
                # reserved probe tenant (this one check is the entire
                # probe-off overhead on the serving hot path)
                from videop2p_tpu.obs.quality import psnr, ssim
                quality = {
                    "edit_psnr": round(float(psnr(videos[1], videos[0])), 4),
                    "edit_ssim": round(float(ssim(videos[1], videos[0])), 4),
                }
            req_dir = os.path.join(self.out_dir, rid)
            os.makedirs(req_dir, exist_ok=True)
            inversion_gif = os.path.join(req_dir, "inversion.gif")
            edit_gif = os.path.join(req_dir, f"{req.get('save_name', 'edit')}.gif")
        with span("serve.gif_write", tracer=self.tracer, trace_id=tid,
                  parent_id=rec.get("span_id"), rid=rid):
            save_video_gif(videos[0], inversion_gif, fps=4)
            save_video_gif(videos[1], edit_gif, fps=4)
        if self.keep_videos:
            self._videos[rid] = videos
        total = time.perf_counter() - rec["submitted_s"]
        self.ledger.record_execute("serve_request_e2e", total, total, tid)
        compile_events = (len(self.ledger.compile_seconds)
                          - rec.get("compile_events_before", 0))
        # the per-request cost vector (ISSUE 19, REQUEST_COST_FIELDS):
        # this slot's fair share of the dispatch plus its own queue
        # seconds; a store hit is additionally credited the inversion it
        # avoided, priced from the same model
        slot = cost_slot or {}
        # a cold request folds in its own fresh-inversion attribution
        # (priced in _resolve); store hits have no entry here — that is
        # exactly the spend they avoided
        inv = self._resolve_costs.pop(rid, None) or {}
        cost = {
            "program": slot.get("program", "serve_edit"),
            "device_seconds": round(slot.get("device_seconds", 0.0)
                                    + inv.get("device_seconds", 0.0), 6),
            "flops": slot.get("flops", 0.0) + inv.get("flops", 0.0),
            "hbm_byte_seconds": (slot.get("hbm_byte_seconds", 0.0)
                                 + inv.get("hbm_byte_seconds", 0.0)),
            "queue_seconds": round(rec.get("queue_wait_s") or 0.0, 6),
            "padding_share": round(slot.get("padding_share", 0.0), 6),
            "saved_device_seconds": 0.0,
            "saved_flops": 0.0,
        }
        store_hit = bool(rec.get("store_hit"))
        if store_hit:
            saved = self.cost.savings()
            cost["saved_device_seconds"] = round(
                saved["saved_device_seconds"], 6)
            cost["saved_flops"] = saved["saved_flops"]
        # program split: the dispatch slot under the edit program, a cold
        # request's fresh inversion under serve_invert — so the
        # per-program ledger joins cleanly against each label's static
        # cost (the parts sum to the tenant's vector)
        programs = [(cost["program"],
                     {**cost,
                      "device_seconds": round(
                          slot.get("device_seconds", 0.0), 6),
                      "flops": slot.get("flops", 0.0),
                      "hbm_byte_seconds": slot.get("hbm_byte_seconds",
                                                   0.0)})]
        if inv:
            programs.append(("serve_invert", inv))
        self.cost.account_request(tenant=rec.get("tenant", "default"),
                                  cost=cost, store_hit=store_hit,
                                  programs=programs)
        self._terminalize(
            rid, "done",
            dispatch_s=round(dispatch_s, 4), total_s=round(total, 4),
            src_err=src_err, compile_events=compile_events,
            cost=cost, content_sha256=content_sha256,
            **(quality or {}),
            inversion_gif=inversion_gif, edit_gif=edit_gif,
        )
        self.ledger.event(
            "serve_request", id=rid, total_s=round(total, 4),
            src_err=src_err, compile_events=compile_events,
            store_hit=self.poll(rid).get("store_hit"),
        )

    def _terminalize(self, rid: str, status: str, **fields) -> bool:
        """Move a record to a terminal status exactly once (the in-flight
        gauge decrements on the transition); False when already terminal."""
        with self._req_lock:
            rec = self._requests.get(rid)
            if rec is None or rec["status"] in TERMINAL_STATUSES:
                return False
            rec["status"] = status
            rec.update(fields)
            self._inflight -= 1
            tenant = rec.get("tenant", "default")
            tid = rec.get("trace_id") if self._tracing else None
            root_span = rec.get("span_id")
            parent = rec.get("_span_parent")
            wall0 = rec.get("_wall_ns")
            submitted = rec.get("submitted_s")
        self._tcount(tenant, {"done": "done", "error": "errors",
                              "deadline_exceeded": "deadline_exceeded",
                              "engine_closed": "engine_closed"}[status])
        if tid:
            # the request's ROOT span closes on EVERY terminal transition
            # (done / error / deadline_exceeded / engine_closed) — a trace
            # with no root is a trace that never terminated
            self.tracer.emit(
                "serve.request", trace_id=tid, span_id=root_span,
                parent_id=parent, wall_ns=wall0,
                duration_s=(time.perf_counter() - submitted
                            if submitted else 0.0),
                status=status, rid=rid, tenant=tenant,
            )
        return True

    def _fail_status(self, rid: str, status: str, message: str,
                     t0: Optional[float] = None) -> None:
        started = t0 if t0 is not None else time.perf_counter()
        if self._terminalize(
            rid, status, error=message,
            total_s=round(time.perf_counter() - started, 4),
        ):
            self.ledger.event("serve_request_error", id=rid, status=status,
                              error=message)

    def _fail(self, rid: str, message: str, t0: float) -> None:
        self._fail_status(rid, "error", message, t0)

    @staticmethod
    def _device_memory() -> List[Dict[str, Any]]:
        out = []
        try:
            for d in jax.local_devices():
                try:
                    ms = d.memory_stats() or {}
                except Exception:  # noqa: BLE001
                    ms = {}
                out.append({
                    "device": d.id,
                    "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                    "bytes_limit": ms.get("bytes_limit"),
                })
        except Exception:  # noqa: BLE001
            pass
        return out
