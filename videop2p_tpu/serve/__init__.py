"""Persistent multi-tenant edit serving (ISSUE 7 — ROADMAP item 1).

The one-shot CLIs pay full program compilation per invocation and repeat
DDIM inversions per edit of the same clip. This package keeps both warm:

  * :mod:`videop2p_tpu.serve.programs` — :class:`ProgramSet`: model
    assembly + scheduler + the instrumented jitted programs (VAE encode,
    capture-inversion, cached-source edit + decode), built once per
    (checkpoint, geometry, steps) :class:`ProgramSpec` key. Controller and
    capture pytrees are traced jit ARGUMENTS, so requests differing only
    in prompts/clips share compiled executables. :class:`ProgramCache` is
    the multi-tenant layer.
  * :mod:`videop2p_tpu.serve.store` — :class:`InversionStore`: a
    byte-budgeted device-resident LRU of inversion products keyed
    content-addressed (``utils/inv_cache``), with optional disk
    write-through of trajectories shared with the CLIs (``--inv_store``).
  * :mod:`videop2p_tpu.serve.batching` — deterministic grouping/padding of
    compatible concurrent requests into one dispatch (bit-exact ``scan``
    mode; data-mesh-sharded ``vmap`` mode).
  * :mod:`videop2p_tpu.serve.engine` — :class:`EditEngine`: the request
    lifecycle (admit → resolve → batch → dispatch → decode) on one worker
    thread, with the run ledger as live SLO telemetry.
  * :mod:`videop2p_tpu.serve.http` / :mod:`videop2p_tpu.serve.client` —
    the stdlib JSON API (``cli/serve.py`` is the entry point) and its
    urllib client (the UI's engine-backed path; ``tools/serve_loadgen.py``).
  * :mod:`videop2p_tpu.serve.sched` — pluggable request schedulers
    (ISSUE 11): ``drain`` (the pre-scheduler engine, pinned bit-exact),
    ``continuous`` (iteration-level admission into the next dispatch),
    ``fair`` (per-tenant priority lanes + deficit-round-robin QoS with
    :class:`TenantConfig` deadline budgets).
  * :mod:`videop2p_tpu.serve.replica` / :mod:`videop2p_tpu.serve.router`
    — the fleet tier: a :class:`ReplicaSupervisor` running N engines over
    ONE shared content-addressed disk inversion store (an inversion on
    replica A is a disk store-hit on replica B), and a stdlib
    :class:`Router` that load-balances on ``/healthz``/``/metrics``,
    routes around open circuit breakers, retries deterministically and
    aggregates fleet health (``cli/router.py`` is the entry point).
  * :mod:`videop2p_tpu.serve.collector` — the fleet telemetry plane's
    ingest half (ISSUE 17): :class:`FleetCollector` scrapes every
    replica's and the router's ``/healthz`` + ``/metrics`` on a fixed
    interval into a bounded :class:`~videop2p_tpu.obs.tsdb.
    TimeSeriesStore` (gaps recorded for dead replicas, never
    interpolated) and evaluates ``obs/signals.py`` burn-rate/trend/
    demand signals on the same cadence.
  * :mod:`videop2p_tpu.serve.prober` — the correctness plane's
    scheduler (ISSUE 20): :class:`FleetProber` runs the
    ``obs/probe.py`` known-answer suite against every replica + the
    router on a deterministic interval under the reserved ``probe``
    tenant, feeds ``probe_success``/``probe_latency`` tsdb series,
    audits canary content hashes fleet-wide and serves per-replica
    quarantine verdicts to the router's pluggable ``probe_status``
    provider.
  * :mod:`videop2p_tpu.serve.faults` — the resilience layer's primitives
    (ISSUE 9): deterministic fault injection (:class:`FaultPlan`), the
    jitter-free :class:`RetryPolicy`, the :class:`CircuitBreaker`, and the
    machine-readable fast-fail exceptions the HTTP layer maps to
    429/503/``Retry-After``.

Import contract: stdlib + numpy + jax (+ the package itself) only — the
same guard as ``obs/`` (tests/test_ledger_schema.py walks this package).
"""

from videop2p_tpu.serve.batching import (
    Batch,
    bucket_size,
    compat_key,
    plan_batches,
    stack_items,
    unstack_outputs,
)
from videop2p_tpu.serve.client import EngineClient, engine_available
from videop2p_tpu.serve.collector import FleetCollector
from videop2p_tpu.serve.prober import FleetProber
from videop2p_tpu.serve.engine import TERMINAL_STATUSES, EditEngine, EditRequest
from videop2p_tpu.serve.faults import (
    CircuitBreaker,
    DeadlineExceeded,
    EngineUnavailable,
    FaultPlan,
    QueueFull,
    RetryPolicy,
    is_transient,
)
from videop2p_tpu.serve.programs import ProgramCache, ProgramSet, ProgramSpec
from videop2p_tpu.serve.replica import Replica, ReplicaSupervisor
from videop2p_tpu.serve.router import Router, RouterServer, make_router_server
from videop2p_tpu.serve.sched import (
    SCHEDULER_POLICIES,
    ContinuousScheduler,
    DrainScheduler,
    FairScheduler,
    Scheduler,
    TenantConfig,
    make_scheduler,
    parse_tenants,
)
from videop2p_tpu.serve.store import (
    InversionStore,
    load_persisted_inversion,
    save_persisted_inversion,
)

__all__ = [
    "Batch",
    "bucket_size",
    "compat_key",
    "plan_batches",
    "stack_items",
    "unstack_outputs",
    "EngineClient",
    "engine_available",
    "FleetCollector",
    "FleetProber",
    "EditEngine",
    "EditRequest",
    "TERMINAL_STATUSES",
    "CircuitBreaker",
    "DeadlineExceeded",
    "EngineUnavailable",
    "FaultPlan",
    "QueueFull",
    "RetryPolicy",
    "is_transient",
    "ProgramCache",
    "ProgramSet",
    "ProgramSpec",
    "InversionStore",
    "load_persisted_inversion",
    "save_persisted_inversion",
    "SCHEDULER_POLICIES",
    "Scheduler",
    "DrainScheduler",
    "ContinuousScheduler",
    "FairScheduler",
    "TenantConfig",
    "make_scheduler",
    "parse_tenants",
    "Replica",
    "ReplicaSupervisor",
    "Router",
    "RouterServer",
    "make_router_server",
]
