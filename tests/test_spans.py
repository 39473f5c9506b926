"""The span primitive (ISSUE 26, obs/spans.py ``span``): nesting and ids
through the context variable, write-on-close, inertness with no ledger,
compile-time attribution by the jax.monitoring listener, ``program.analysis``,
``phase_timer`` as a thin caller, the device-side named scopes, and the
vocabulary the benchmark reads held against a tiny ``run_tuning.main``."""

import contextvars
import functools
import importlib.util
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.obs import RunLedger, instrumented_jit, read_ledger
from videop2p_tpu.obs import ledger as obs_ledger
from videop2p_tpu.obs import spans as obs_spans
from videop2p_tpu.obs.ledger import program_label
from videop2p_tpu.obs.spans import (
    BENCHMARK_SPAN_NAMES,
    SPAN_EVENT_FIELDS,
    Tracer,
    current_span,
    span,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spans(path):
    return [e for e in read_ledger(path) if e["event"] == "span"]


def _one(spans, name):
    found = [s for s in spans if s["name"] == name]
    assert len(found) == 1, (name, [s["name"] for s in spans])
    return found[0]


# -------------------------------------------------- the primitive itself ---


def test_span_nesting_ids_and_worker_threads(tmp_path):
    """parent_id is the enclosing span's and trace_id the run's, through the
    context variable; a copied context carries the parent into a worker
    thread, a bare thread starts a root of the same run."""
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        with span("outer", a=1) as outer:
            assert current_span() is outer
            with span("inner"):
                pass
            ctx = contextvars.copy_context()
            carried = threading.Thread(
                target=lambda: ctx.run(lambda: span("carried").__enter__()
                                       .end()))
            bare = threading.Thread(
                target=lambda: span("bare").__enter__().end())
            for t in (carried, bare):
                t.start()
                t.join()
        assert current_span() is None
        with span("request", trace_id="ab" * 16, parent_id="cd" * 8):
            with span("inherits"):
                pass
    spans = _spans(path)
    outer_ev = _one(spans, "outer")
    for s in spans:
        assert set(SPAN_EVENT_FIELDS) <= set(s)
        assert len(s["span_id"]) == 16 and s["duration_s"] >= 0
    assert outer_ev["parent_id"] is None and outer_ev["a"] == 1
    assert outer_ev["trace_id"] == led.trace_id and len(led.trace_id) == 32
    assert _one(spans, "inner")["parent_id"] == outer_ev["span_id"]
    assert _one(spans, "carried")["parent_id"] == outer_ev["span_id"]
    assert _one(spans, "bare")["parent_id"] is None
    assert _one(spans, "bare")["trace_id"] == led.trace_id
    # an explicit trace (the engine's request) is inherited by what it encloses
    req = _one(spans, "request")
    assert req["trace_id"] == "ab" * 16 and req["parent_id"] == "cd" * 8
    inherits = _one(spans, "inherits")
    assert inherits["trace_id"] == "ab" * 16
    assert inherits["parent_id"] == req["span_id"]
    # children close, and are written, before their parents
    order = [s["name"] for s in spans]
    assert order.index("inner") < order.index("outer")


def test_span_is_on_disk_when_an_exception_unwinds_and_close_never_runs(
        tmp_path):
    """The benchmark ends ``main`` by raising through it: ``close()`` is never
    reached, and every span that closed is in the file all the same."""
    path = str(tmp_path / "ledger.jsonl")
    led = RunLedger(path).activate()

    class WindowClosed(Exception):
        pass

    def main():
        with span("main.root") as root:
            with span("main.first"):
                pass
            root.end()  # the handle: closes early; later closes do nothing
            with span("main.loop"):
                raise WindowClosed()

    try:
        with pytest.raises(WindowClosed):
            main()
        # read while the ledger is still open and active: nothing is buffered
        events = read_ledger(path)
        assert not any(e["event"] == "run_end" for e in events)
        spans = [e for e in events if e["event"] == "span"
                 and not e["name"].startswith("process")]
        assert [s["name"] for s in spans] == ["main.first", "main.root",
                                              "main.loop"]
        assert _one(spans, "main.root")["status"] == "ok"
        assert _one(spans, "main.loop")["status"] == "error"
        # main.loop opened after the root's end(): a root of its own
        assert _one(spans, "main.loop")["parent_id"] is None
        assert current_span() is None
    finally:
        led.close()


def test_no_ledger_or_tracing_off_writes_nothing_and_mints_no_id(
        tmp_path, monkeypatch):
    minted = []
    real = obs_spans.make_span_id
    monkeypatch.setattr(obs_spans, "make_span_id",
                        lambda: minted.append(1) or real())
    assert obs_ledger.current_ledger() is None
    with span("nobody.listens", x=1) as s:
        assert current_span() is None and not s.live
        with span("nor.here"):
            pass
    assert s.span_id is None and s.elapsed() >= 0 and minted == []
    # a ledger whose tracer is off (the engine with tracing off) is the same
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.tracer = Tracer(led, enabled=False)
        with span("tracing.off") as s:
            pass
        f = instrumented_jit(lambda x: x + 1, program="toy_off",
                             analyze=False)
        f(jnp.ones(3))
    assert s.span_id is None and minted == []
    events = read_ledger(path)
    assert not any(e["event"] == "span" for e in events)
    # ... and the program_call event keeps its fields either way
    call, = [e for e in events if e["event"] == "program_call"]
    assert {"program", "cache_miss", "dispatch_s"} <= set(call)


def test_handle_end_is_idempotent_and_counters_accumulate(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        with span("counted") as s:
            s.count("steps", 2)
            s.count("steps", 3)
            s.set(note="x")
            s.end()
            s.end(status="error")  # nothing: it has closed
    ev = _one(_spans(path), "counted")
    assert ev["steps"] == 5 and ev["note"] == "x"
    assert ev["status"] == "ok"


# ------------------------------------------- compile-time attribution ---

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def test_listener_turns_durations_into_children_and_sums_unlabelled(
        tmp_path, monkeypatch):
    """jax's trace / lower / backend-compile durations fired under a program
    label become children of the open span (nested ones collapse into the
    outermost); fired with no label they are summed on the open span, or on
    the ledger where none is open — never one line each. The wall clock the
    listener and the spans read is the test's own: a child's start is "now
    less its duration", and a worker under load sleeps longer than asked."""
    fire = jax.monitoring.record_event_duration_secs
    clock = [time.time_ns()]
    monkeypatch.setattr(time, "time_ns", lambda: clock[0])

    def spend(seconds):
        clock[0] += int(seconds * 1e9)

    def took(event, seconds, slept=None):
        # jax reports a duration when the work ends: spend it, then report
        spend(seconds if slept is None else slept)
        fire(event, seconds)

    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        n_compile_events = len(led.compile_seconds)
        with program_label("prog"), span("program.call", program="prog"):
            with span("program.execute"):
                took(_TRACE, 0.002)             # an inner jit's trace ...
                took(_TRACE, 0.010, slept=0.006)  # ... inside the outer one
                took(_LOWER, 0.003)
                jax.monitoring.record_event(
                    "/jax/compilation_cache/compile_requests_use_cache")
                jax.monitoring.record_event(
                    "/jax/compilation_cache/cache_hits")
                spend(0.005)
                fire(_RETRIEVAL, 0.004)
                fire(_BACKEND, 0.005)
        assert len(led.compile_seconds) == n_compile_events + 1
        with span("eager.region"):
            for _ in range(3):
                fire(_TRACE, 0.001)
            fire(_BACKEND, 0.25)
        fire(_BACKEND, 0.5)  # no label, no span open: the ledger's counter
        fire(_LOWER, 0.001)
        assert led.counters == {"unspanned_backend_compile_s": 0.5,
                                "unspanned_backend_compiles": 1,
                                "unspanned_trace_lower_events": 1}
    events = read_ledger(path)
    spans = [e for e in events if e["event"] == "span"]
    call = _one(spans, "program.call")
    kids = [s for s in spans if s["parent_id"] == call["span_id"]]
    assert sorted(s["name"] for s in kids) == [
        "program.backend_compile", "program.execute", "program.lower",
        "program.trace"]
    trace = _one(kids, "program.trace")
    assert trace["duration_s"] == pytest.approx(0.010) and trace["nested"] == 1
    backend = _one(kids, "program.backend_compile")
    assert backend["cache_hit"] is True
    assert backend["cache_retrieval_s"] == pytest.approx(0.004)
    # start = now − duration: the child lies inside its parent
    assert backend["wall_ns"] >= call["wall_ns"] - int(0.02e9)
    eager = _one(spans, "eager.region")
    assert eager["unspanned_trace_lower_events"] == 3
    assert eager["unspanned_backend_compiles"] == 1
    assert eager["unspanned_backend_compile_s"] == pytest.approx(0.25)
    # the call, its four children, the eager region (and the ledger's process)
    assert len([s for s in spans if not s["name"].startswith("process")]) == 6
    end, = [e for e in events if e["event"] == "run_end"]
    assert end["unspanned_backend_compiles"] == 1


def _kids(spans, parent, name=None):
    return [s for s in spans if s["parent_id"] == parent["span_id"]
            and (name is None or s["name"] == name)]


def _subtree(spans, root):
    out, todo = [], [root]
    while todo:
        found = _kids(spans, todo.pop())
        out += found
        todo += found
    return out


def test_program_analysis_encloses_the_analysis_and_only_it(tmp_path):
    """On a jit-cache miss the introspection pass runs under
    ``program.analysis``: it reads the executable the call built (no
    lowering and no backend compile of its own: ``rebuilt`` false) in parts
    that are its children; the call's trace, lowering and compile are the
    call's; ``program.execute`` starts where the compile ended; a hit has
    neither compile children nor an analysis."""
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path, latency=True) as led:
        f = instrumented_jit(lambda x: jnp.tanh(x) @ x.T,
                             program="toy_analysis",
                             span_attrs=lambda x: {"rows": x.shape[0]})
        x = jnp.ones((32, 32)) * 0.37
        f(x)
        n_compiles = len(led.compile_seconds)
        f(x)
        assert len(led.compile_seconds) == n_compiles
    events = read_ledger(path)
    spans = [e for e in events if e["event"] == "span"]
    miss, hit = [s for s in spans if s["name"] == "program.call"]
    assert miss["cache_miss"] is True and hit["cache_miss"] is False
    assert miss["program"] == "toy_analysis" and miss["rows"] == 32

    kids = functools.partial(_kids, spans)
    analysis, = kids(miss, "program.analysis")
    execute, = kids(miss, "program.execute")
    own_compile, = kids(miss, "program.backend_compile")
    assert kids(miss, "program.trace") and kids(miss, "program.lower")
    # what is left in the analysis is the reading, part by part
    assert analysis["rebuilt"] is False
    assert {s["name"] for s in kids(analysis)} - {"program.trace"} == {
        "analysis.text", "analysis.cost", "analysis.memory", "analysis.mine",
        "analysis.comm"}
    assert len(kids(miss, "program.backend_compile")) == 1
    # exactly one `compile` event for the program: the call's own
    assert len([e for e in events if e["event"] == "compile"
                and e["program"] == "toy_analysis"]) == 1
    # it encloses the analysis: the program_analysis event is written inside
    # its interval, the program_call event before it opens
    t_end = {s["span_id"]: s["t"] for s in spans}
    pa, = [e for e in events if e["event"] == "program_analysis"]
    pc = [e for e in events if e["event"] == "program_call"][0]
    assert (t_end[analysis["span_id"]] - analysis["duration_s"] - 1e-3
            <= pa["t"] <= t_end[analysis["span_id"]])
    assert pc["t"] <= t_end[analysis["span_id"]] - analysis["duration_s"] + 1e-3
    # execute starts where the call's compile ended, and ends before analysis
    compile_end = own_compile["wall_ns"] * 1e-9 + own_compile["duration_s"]
    assert execute["wall_ns"] * 1e-9 >= compile_end - 1e-3
    assert (execute["wall_ns"] * 1e-9 + execute["duration_s"]
            <= analysis["wall_ns"] * 1e-9 + 1e-3)
    assert "blocked" not in execute  # --latency: the wrapper blocked
    # the hit: execute only
    assert [s["name"] for s in kids(hit)] == ["program.execute"]
    # the children of the miss make it up (within the wrapper's own overhead)
    assert sum(s["duration_s"] for s in kids(miss)) <= miss["duration_s"] + 1e-3


# Each case: (traced) -> (function, jit options, [(args, kwargs) a call]).
# Every call has a signature of its own, so every call is a miss; `traced`
# is appended to whenever jax runs the Python body.


def _case_weak_scalar(traced):
    def f(step, x):
        traced.append(1)
        return step + 1, jnp.tanh(x) @ x.T * step

    step = jnp.asarray(0)  # weak-typed, as TrainState.create makes it
    assert step.weak_type
    return f, {}, [((step, jnp.ones((16, 16))), {})]


def _case_train_state(traced):
    """``run_tuning.main``'s own wrapping of ``loss_steps``: the state
    donated, the step count static, ``state.step`` weak through the scan."""
    from videop2p_tpu.train import TrainState, TuneConfig, make_optimizer
    from videop2p_tpu.train.tuner import StepLoss, loss_steps

    params = {"blk": {"attn1": {"to_q": {"kernel": jnp.full((8, 8), 0.1)}},
                      "proj": {"kernel": jnp.full((8, 8), 0.2, jnp.bfloat16)}}}
    tx = make_optimizer(TuneConfig(learning_rate=1e-3))
    state = TrainState.create(params, tx)
    assert state.step.weak_type and jax.tree.leaves(state.frozen)

    def loss(p, drawn):
        y = drawn @ p["blk"]["attn1"]["to_q"]["kernel"]
        y = y @ p["blk"]["proj"]["kernel"].astype(jnp.float32)
        return jnp.mean(y ** 2), {}

    step_loss = StepLoss(draw=lambda key: jax.random.normal(key, (4, 8)),
                         loss=loss)

    def program(s, k, n):
        traced.append(1)
        return loss_steps(step_loss, tx, s, k, num_steps=n)

    return (program, {"static_argnums": 2, "donate_argnums": (0,)},
            [((state, jax.random.key(0), 3), {})])


def _case_typed_key(traced):
    def f(key, x):
        traced.append(1)
        return x + jax.random.normal(key, x.shape)

    return f, {}, [((jax.random.key(7), jnp.zeros((8, 4))), {})]


def _case_bfloat16(traced):
    def f(w, x):
        traced.append(1)
        return (x.astype(jnp.bfloat16) @ w).astype(jnp.float32)

    return f, {}, [((jnp.ones((8, 8), jnp.bfloat16), jnp.ones((4, 8))), {})]


def _case_keywords(traced):
    def f(x, *, scale, shift):
        traced.append(1)
        return x * scale + shift

    return f, {}, [((jnp.ones((4, 4)),),
                    {"scale": jnp.asarray(2.0), "shift": jnp.ones((4,))})]


def _case_committed_leaf(traced):
    """A leaf put on a device by name is COMMITTED: the call lowers with its
    sharding as the argument's, and so must the analysis."""
    def f(x, y):
        traced.append(1)
        return jnp.tanh(x) @ y

    x = jax.device_put(jnp.ones((8, 8)), jax.devices()[0])
    assert x._committed
    return f, {}, [((x, jnp.ones((8, 8))), {})]


def _case_second_shape(traced):
    def f(x):
        traced.append(1)
        return jnp.tanh(x) @ x.T

    return f, {}, [((jnp.ones((8, 8)),), {}), ((jnp.ones((16, 8)),), {})]


@pytest.mark.parametrize("case", [
    _case_weak_scalar, _case_train_state, _case_typed_key, _case_bfloat16,
    _case_keywords, _case_committed_leaf, _case_second_shape,
], ids=lambda c: c.__name__[len("_case_"):])
def test_a_miss_builds_its_program_once(tmp_path, case):
    """A miss through ``instrumented_jit`` traces the Python body once and
    fires one lowering and one backend compile IN ALL: the analysis is
    handed the call's own build (``rebuilt`` false, no ``program.lower`` /
    ``program.backend_compile`` under it) and still writes its record."""
    traced = []
    fun, jit_kwargs, calls = case(traced)
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        f = instrumented_jit(fun, program="once", **jit_kwargs)
        for args, kwargs in calls:
            jax.block_until_ready(f(*args, **kwargs))
    events = read_ledger(path)
    spans = [e for e in events if e["event"] == "span"]
    misses = [s for s in spans if s["name"] == "program.call"]
    assert len(misses) == len(calls) == len(traced)
    for miss in misses:
        assert miss["cache_miss"] is True
        names = [s["name"] for s in _subtree(spans, miss)]
        assert names.count("program.lower") == 1, names
        assert names.count("program.backend_compile") == 1, names
        analysis, = _kids(spans, miss, "program.analysis")
        assert analysis["rebuilt"] is False
        under = {s["name"] for s in _subtree(spans, analysis)}
        assert not under & {"program.lower", "program.backend_compile"}, under
        assert len(_kids(spans, miss, "program.lower")) == 1
        assert len(_kids(spans, miss, "program.backend_compile")) == 1
    assert not [e for e in events if e["event"] == "program_analysis_skipped"]
    records = [e for e in events if e["event"] == "program_analysis"]
    assert len(records) == len(calls)
    for rec in records:
        assert rec["program"] == "once"
        assert rec["flops"] >= 0 and rec["peak_hbm_bytes"] > 0
        assert len(rec["hlo_fingerprint"]) == 16
    # the run's compile totals hold each build once
    assert len([e for e in events if e["event"] == "compile"
                and e["program"] == "once"]) == len(calls)


def test_an_analysis_that_builds_again_says_rebuilt(tmp_path, monkeypatch):
    """The abstraction as it was before (shape and dtype alone) asks jax for
    another signature than the call's wherever a leaf is weak-typed: the
    analysis then traces, lowers and compiles the program a second time,
    and ``rebuilt`` says so — a JAX upgrade that stops sharing the call's
    build fails HERE instead of costing every run its compile again."""
    from videop2p_tpu.obs import introspect

    def shape_and_dtype_only(args, kwargs):
        def to_abstract(leaf):
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            return leaf

        return (jax.tree.map(to_abstract, args),
                jax.tree.map(to_abstract, kwargs))

    monkeypatch.setattr(introspect, "abstractify_args", shape_and_dtype_only)
    traced = []
    fun, jit_kwargs, calls = _case_weak_scalar(traced)
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        f = instrumented_jit(fun, program="twice", **jit_kwargs)
        for args, kwargs in calls:
            f(*args, **kwargs)
    events = read_ledger(path)
    spans = [e for e in events if e["event"] == "span"]
    miss, = [s for s in spans if s["name"] == "program.call"]
    analysis, = _kids(spans, miss, "program.analysis")
    assert analysis["rebuilt"] is True and len(traced) == 2
    assert {"program.lower", "program.backend_compile"} <= {
        s["name"] for s in _kids(spans, analysis)}
    # the second build is the analysis': the run's totals hold the call's
    assert len([e for e in events if e["event"] == "compile"
                and e["program"] == "twice"]) == 1
    assert [e["program"] for e in events
            if e["event"] == "program_analysis"] == ["twice"]


def test_execute_span_never_adds_a_sync(tmp_path, monkeypatch):
    """Where the run does not block (no --latency) the span is the dispatch
    alone and says so; it calls block_until_ready nowhere."""
    monkeypatch.delenv("VIDEOP2P_OBS_LATENCY", raising=False)
    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(1) or real(x))
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        f = instrumented_jit(lambda x: x * 2, program="toy_async",
                             analyze=False)
        f(jnp.ones(4))
    assert blocked == []
    execute = _one(_spans(path), "program.execute")
    assert execute["blocked"] is False


def test_phase_timer_still_prints_and_writes_phase_and_is_a_span(
        tmp_path, capsys):
    from videop2p_tpu.utils.profiling import phase_timer

    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        with span("enclosing"):
            with phase_timer("some_phase", count=4, unit="it"):
                pass
    with phase_timer("no_ledger_phase"):
        pass
    out = capsys.readouterr().out
    assert "[phase] some_phase:" in out and "ms/it" in out
    assert "[phase] no_ledger_phase:" in out
    events = read_ledger(path)
    phase, = [e for e in events if e["event"] == "phase"]
    assert phase["name"] == "some_phase" and phase["count"] == 4
    spans = [e for e in events if e["event"] == "span"]
    ph = _one(spans, "some_phase")
    assert ph["parent_id"] == _one(spans, "enclosing")["span_id"]
    assert ph["count"] == 4 and ph["unit"] == "it"
    assert abs(ph["duration_s"] - phase["seconds"]) < 0.05


# ----------------------------------------------------- device-side names ---


def test_train_steps_lowering_carries_scope_names_forward_and_backward():
    """``jax.named_scope`` names are metadata on the ops: the lowered tune
    program carries ``ops.frame_attention`` and ``ops.group_norm`` under
    ``train.loss`` on forward (jvp) AND backward (transpose) ops, and
    ``train.noise`` / ``train.optimizer`` beside them."""
    from videop2p_tpu.core import DDPMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import make_unet_fn
    from videop2p_tpu.train import (TrainState, TuneConfig, make_optimizer,
                                    train_steps)

    cfg = UNet3DConfig.tiny()
    cfg = type(cfg)(**{**cfg.__dict__, "frame_attention": "chunked",
                       "gradient_checkpointing": True})
    model = UNet3DConditionModel(config=cfg)
    latents = jnp.zeros((1, 2, 8, 8, 4))
    text = jnp.zeros((1, 7, cfg.cross_attention_dim))
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(2), latents, jnp.asarray(0), text))
    tx = make_optimizer(TuneConfig(learning_rate=1e-3))
    state = jax.eval_shape(
        lambda p: TrainState.create(p, tx), variables["params"])
    lowered = jax.jit(
        lambda s, k: train_steps(make_unet_fn(model), tx, s,
                                 DDPMScheduler.create_sd(), latents, text, k,
                                 num_steps=2)
    ).lower(state, jax.random.key(0))
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))

    def some(*parts):
        return [n for n in names if all(p in n for p in parts)]

    # forward ops: train.loss/jvp(<module>)/<flax path>/ops.frame_attention/…
    # backward ops: train.loss/transpose(jvp(<module>))/…, the recompute of a
    # checkpointed block under …/checkpoint/rematted_computation/…
    forward = [n for n in names if n.startswith("train.loss/jvp(")]
    backward = [n for n in names if n.startswith("train.loss/transpose(jvp(")]
    for scope in ("ops.frame_attention", "ops.group_norm"):
        assert [n for n in forward if scope in n], scope
        assert [n for n in backward if scope in n], scope
    assert [n for n in backward if "rematted_computation" in n]
    assert some("train.noise/") and some("train.optimizer/")
    # the optimizer's ops are not the loss's, nor the other way round
    assert not some("train.optimizer", "train.loss")


# ------------------------------- the vocabulary the benchmark reads ---


def _benchmark_spans_module():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_spans",
        os.path.join(_REPO, "benchmark", "harness", "spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_tune_ledger(tmp_path_factory):
    """A tiny ``run_tuning.main`` ended the way the benchmark's driver ends
    it: an exception raised through ``main`` at the third call, so that
    ``run_ledger.close()`` is never reached."""
    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import load_config

    class WindowClosed(Exception):
        pass

    out = tmp_path_factory.mktemp("tiny_tune")
    cfg = load_config(os.path.join(_REPO, "configs", "rabbit-jump-tune.yaml"))
    cfg["output_dir"] = str(out / "run")
    cfg["train_data"].update(
        video_path=os.path.join(_REPO, "data", "rabbit"),
        n_sample_frames=2, width=16, height=16)
    cfg.update(max_train_steps=10 ** 6, steps_per_call=2, log_every=2,
               checkpointing_steps=0, validation_steps=0, latency=True)
    path = str(out / "ledger.jsonl")
    real_jit = run_tuning.instrumented_jit
    calls = []

    def counting_jit(fn, **kw):
        prog = real_jit(fn, **kw)

        def steps_fn(*args):
            if len(calls) == 3:
                raise WindowClosed()
            calls.append(1)
            return prog(*args)

        return steps_fn

    run_tuning.instrumented_jit = counting_jit
    try:
        with pytest.raises(WindowClosed):
            run_tuning.main(**cfg, tiny=True, ledger=path)
    finally:
        run_tuning.instrumented_jit = real_jit
        led = obs_ledger.current_ledger()
        events = read_ledger(path)  # before anything closes it
        if led is not None and led.path == path:
            led.close()
    return events


def test_the_names_the_benchmark_reads_are_one_tuple_and_main_emits_each(
        tiny_tune_ledger):
    """A rename of a span the benchmark reads fails HERE, before it turns a
    listed metric to null: the benchmark's helper (which may import nothing
    of the program) keeps the same tuple, and a tiny ``main`` emits each."""
    assert _benchmark_spans_module().READ_NAMES == BENCHMARK_SPAN_NAMES
    assert not any(e["event"] == "run_end" for e in tiny_tune_ledger)
    spans = [e for e in tiny_tune_ledger if e["event"] == "span"]
    names = {s["name"] for s in spans}
    assert set(BENCHMARK_SPAN_NAMES) <= names, set(BENCHMARK_SPAN_NAMES) - names
    # ... and the sites the benchmark does not read yet
    assert {"models.init_or_load", "tune.metrics_logger",
            "tune.flush_losses"} <= names
    root = _one(spans, "tune.setup")
    in_order = [s["name"] for s in sorted(
        (s for s in spans if s["parent_id"] == root["span_id"]),
        key=lambda s: s["wall_ns"])]
    assert in_order == [
        "tune.build_models", "tune.load_clip", "tune.vae_encode",
        "tune.text_encode", "tune.state_create", "tune.metrics_logger",
        "program.call", "tune.flush_losses"]
    calls = sorted((s for s in spans if s["name"] == "program.call"),
                   key=lambda s: s["wall_ns"])
    assert len(calls) == 3 and all(c["steps"] == 2 for c in calls)
    assert all(c["program"] == "train_steps" for c in calls)
    # the root closed at the end of the first chunk's bookkeeping: the later
    # calls are roots of the same run
    assert [c["parent_id"] for c in calls[1:]] == [None, None]
    assert {c["trace_id"] for c in calls} == {root["trace_id"]}
    # the phase event keeps its place beside the span of its name
    assert [e["name"] for e in tiny_tune_ledger
            if e["event"] == "phase"] == ["tune.vae_encode"]


def test_the_benchmarks_readers_make_up_the_setup_on_a_tiny_main(
        tiny_tune_ledger, tmp_path, monkeypatch):
    bench = _benchmark_spans_module()
    path = tmp_path / "ledger.jsonl"
    import json

    path.write_text("".join(json.dumps(e) + "\n" for e in tiny_tune_ledger))
    monkeypatch.setattr(bench, "ledger_path", lambda ctx: str(path))
    ctx = {"cell": {"name": "x"}, "window": {"calls": [{}, {}]}}
    parts = bench.setup_parts(ctx)
    assert all(v is not None for v in parts.values()), parts
    named = sum(parts[k] for k in ("models", "clip", "trace_lower", "load",
                                   "analysis", "execute", "unattributed"))
    assert named == pytest.approx(parts["setup"])
    # the analysis pass read the program the first call built: one lowering
    # and one backend compile in the whole call, neither under the analysis
    assert parts["analysis"] > 0 and parts["trace_lower"] > 0
    spans = [e for e in tiny_tune_ledger if e["event"] == "span"]
    first = min((s for s in spans if s["name"] == "program.call"
                 and s["program"] == "train_steps"),
                key=lambda s: s["wall_ns"])
    names = [s["name"] for s in _subtree(spans, first)]
    assert names.count("program.lower") == 1, names
    assert names.count("program.backend_compile") == 1, names
    analysis, = _kids(spans, first, "program.analysis")
    assert analysis["rebuilt"] is False
    assert not {"program.lower", "program.backend_compile"} & {
        s["name"] for s in _subtree(spans, analysis)}
    assert [e["program"] for e in tiny_tune_ledger
            if e["event"] == "program_analysis"] == ["train_steps"]
    assert bench.host_between_calls_ms(ctx) > 0


# ------------------------------- the set-up before the root: `process` ---


def _end_ns(s):
    return s["wall_ns"] + int(round(s["duration_s"] * 1e9))


def test_process_reaches_from_the_process_start_to_the_root(tiny_tune_ledger):
    """One ``process`` a ledger, written when the first live span opened: it
    ends where ``tune.setup`` starts, starts no later than the package's
    first line, and holds ``process.import`` then ``process.ledger_open``,
    apart; the root's own children are as they were."""
    spans = [e for e in tiny_tune_ledger if e["event"] == "span"]
    process, root = _one(spans, "process"), _one(spans, "tune.setup")
    assert abs(_end_ns(process) - root["wall_ns"]) <= 1e6
    assert process["parent_id"] is None and process["anchor"] == "proc"
    assert process["trace_id"] == root["trace_id"]
    kids = sorted(_kids(spans, process), key=lambda s: s["wall_ns"])
    assert [s["name"] for s in kids] == ["process.import",
                                         "process.ledger_open"]
    imported, opened = kids
    assert process["wall_ns"] <= imported["wall_ns"]
    assert _end_ns(imported) <= opened["wall_ns"]
    assert _end_ns(opened) <= root["wall_ns"]
    import videop2p_tpu

    assert imported["wall_ns"] == videop2p_tpu.IMPORT_NS
    # the first live span wrote it, once
    assert [s["name"] for s in spans[:3]] == [
        "process.import", "process.ledger_open", "process"]


def test_process_starts_at_the_packages_first_line_where_proc_is_unread(
        tmp_path, monkeypatch):
    import videop2p_tpu

    monkeypatch.setattr(obs_spans, "_PROC_STAT", str(tmp_path / "absent"))
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path):
        with span("first") as first:
            pass
        with span("second"):
            pass
    spans = _spans(path)
    process = _one(spans, "process")
    assert process["anchor"] == "import"
    assert process["wall_ns"] == videop2p_tpu.IMPORT_NS
    assert _end_ns(process) == pytest.approx(first._wall_ns, abs=1e3)
    assert [s["name"] for s in spans].count("process.ledger_open") == 1


def test_the_kernels_start_time_is_read_after_the_last_parenthesis(
        tmp_path, monkeypatch):
    """Field 2 of ``/proc/<pid>/stat`` is the command name in parentheses,
    which may itself hold spaces and parentheses; field 22 is the start in
    clock ticks since boot."""
    tick = os.sysconf("SC_CLK_TCK")
    ticks = int((time.clock_gettime(time.CLOCK_BOOTTIME) - 5.0) * tick)
    fields = ["S"] + ["0"] * 18 + [str(ticks)] + ["0"] * 30
    stat = tmp_path / "stat"
    stat.write_text("4242 (a) b (c) " + " ".join(fields) + "\n")
    monkeypatch.setattr(obs_spans, "_PROC_STAT", str(stat))
    start_ns, anchor = obs_spans.process_start_ns()
    assert anchor == "proc"
    assert (time.time_ns() - start_ns) * 1e-9 == pytest.approx(5.0, abs=0.05)


def test_first_spans_of_many_threads_write_one_process(tmp_path):
    import sys

    path = str(tmp_path / "ledger.jsonl")
    n = 4 * (os.cpu_count() or 1)
    barrier = threading.Barrier(n)

    def first_span():
        barrier.wait(timeout=30)
        with span("worker"):
            pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with RunLedger(path):
            threads = [threading.Thread(target=first_span) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    names = [s["name"] for s in _spans(path)]
    assert names.count("process") == 1 and names.count("worker") == n
    assert names.count("process.ledger_open") == 1


def test_a_ledger_whose_tracer_is_off_writes_no_process(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        led.tracer.enabled = False
        with span("tracing.off"):
            pass
    assert not any(e["event"] == "span" for e in read_ledger(path))
    # the engine's own tracer, put in place of the ledger's: no `process`
    path = str(tmp_path / "engine.jsonl")
    with RunLedger(path) as led:
        led.tracer = Tracer(led, enabled=True)
        with span("engine.span"):
            pass
    assert [s["name"] for s in _spans(path)] == ["engine.span"]


def test_the_tensorboard_writer_is_a_span_under_the_logger(
        tiny_tune_ledger, tmp_path):
    """On a tiny ``main`` the event file writer's construction is one span
    under ``tune.metrics_logger``, naming its format; where the writer
    cannot be built (a plain file where its ``tb/`` directory goes) the span
    closes ``error`` and the logger goes on with its JSONL alone."""
    from videop2p_tpu.utils.metrics import MetricsLogger

    spans = [e for e in tiny_tune_ledger if e["event"] == "span"]
    writer = _one(spans, "metrics.tensorboard_writer")
    logger = _one(spans, "tune.metrics_logger")
    assert writer["parent_id"] == logger["span_id"]
    assert writer["duration_s"] <= logger["duration_s"]
    assert writer["status"] == "ok" and writer["format"] == "tfevents"
    run = tmp_path / "run"
    run.mkdir()
    (run / "tb").write_text("not a directory")
    path = str(tmp_path / "ledger.jsonl")
    with RunLedger(path) as led:
        with span("tune.metrics_logger"):
            metrics = MetricsLogger(str(run), ledger=led)
        metrics.log(1, {"train_loss": 1.0})
        metrics.close()
    failed = _one(_spans(path), "metrics.tensorboard_writer")
    assert failed["status"] == "error" and metrics._tb is None
    assert failed["parent_id"] == _one(_spans(path),
                                       "tune.metrics_logger")["span_id"]
    with open(metrics.path) as f:
        assert len(f.readlines()) == 1


def test_the_process_names_the_benchmark_reads_are_one_tuple(
        tiny_tune_ledger):
    """``benchmark/harness/process_spans.py`` keeps the tuple as READ_NAMES
    (read here from its text: it may import nothing of the program, and the
    program's tests import nothing of the benchmark); a tiny ``main`` emits
    each name; the older tuple is untouched."""
    import ast

    with open(os.path.join(_REPO, "benchmark", "harness",
                           "process_spans.py")) as f:
        tree = ast.parse(f.read())
    read_names, = [ast.literal_eval(node.value) for node in tree.body
                   if isinstance(node, ast.Assign)
                   and [t.id for t in node.targets] == ["READ_NAMES"]]
    assert read_names == obs_spans.BENCHMARK_PROCESS_SPAN_NAMES
    names = {e["name"] for e in tiny_tune_ledger if e["event"] == "span"}
    assert set(read_names) <= names, set(read_names) - names
    assert not set(read_names) & set(BENCHMARK_SPAN_NAMES)


# --------------------------- the hybrid token model's scopes and counters ---


def _token_family_main_emits(module, cfg, yaml_name):
    """The named scopes in the family's lowered loss, forward and backward,
    and the ``metric`` events of a tiny ``run_tuning.main`` on its YAML (two
    calls of two steps) with ``tune.load_document`` under the root."""
    import tempfile

    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import load_config

    params = module.abstract_params(cfg, jnp.float32)["params"]
    ids = jax.ShapeDtypeStruct((32,), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p, i: module.forward_loss(p, cfg, i, jnp.float32)[0])).lower(
            params, ids).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in module.SCOPES:
        mine = [n for n in names if scope in n]
        assert [n for n in mine if f"jvp({scope})" in n], scope   # forward
        assert [n for n in mine if "transpose(" in n], scope      # backward

    class WindowClosed(Exception):
        pass

    real_jit, calls = run_tuning.instrumented_jit, []

    def counting_jit(fn, **kw):
        prog = real_jit(fn, **kw)

        def steps_fn(*args):
            if len(calls) == 2:
                raise WindowClosed()
            calls.append(1)
            return prog(*args)

        return steps_fn

    config = load_config(os.path.join(_REPO, "configs", yaml_name))
    with tempfile.TemporaryDirectory() as out:
        path = os.path.join(out, "ledger.jsonl")
        config.update(output_dir=os.path.join(out, "run"),
                      train_data={"n_tokens": 32, "document_seed": 1},
                      max_train_steps=10 ** 6, steps_per_call=2, log_every=2,
                      checkpointing_steps=0, validation_steps=0)
        run_tuning.instrumented_jit = counting_jit
        try:
            with pytest.raises(WindowClosed):
                run_tuning.main(**config, tiny=True, ledger=path)
        finally:
            run_tuning.instrumented_jit = real_jit
            led = obs_ledger.current_ledger()
            events = read_ledger(path)
            if led is not None and led.path == path:
                led.close()
    spans = [e for e in events if e["event"] == "span"]
    root = _one(spans, "tune.setup")
    document = _one(spans, "tune.load_document")
    assert document["parent_id"] == root["span_id"] and document["tokens"] == 32
    metrics = [e for e in events if e["event"] == "metric"]
    assert len(metrics) == 4  # two calls of two steps
    for rec in metrics:
        for name in module.COUNTERS + ("train_loss",):
            assert np.isfinite(rec[name]), (name, rec)
    return metrics


def test_hybrid_token_model_main_emits_the_scopes_and_counters_read():
    """What the hybrid cell's per-layer metrics read by NAME is one pair of
    tuples (``models/granite_hybrid.SCOPES`` / ``COUNTERS``): every scope is
    in the lowered loss, forward and backward, and a
    tiny ``run_tuning.main`` logs every counter a step beside the loss and
    emits ``tune.load_document`` under the root."""
    from videop2p_tpu.models import granite_hybrid as gh

    assert gh.SCOPES == ("lm.mamba_proj", "lm.ssd", "lm.attention",
                         "lm.router", "lm.experts", "lm.shared_expert",
                         "lm.head_loss")
    assert gh.COUNTERS == ("expert_load_max_over_mean", "held_pair_share",
                           "routed_over_shared", "ssd_state_rms")
    metrics = _token_family_main_emits(gh, gh.GraniteHybridConfig.tiny(),
                                       "granite-4.0-h-small-s4-tune.yaml")
    assert metrics[0]["ssd_state_rms"] > 0


def test_third_token_family_main_emits_the_scopes_and_counters_read():
    """The same for ``models/cohere2_moe.SCOPES`` / ``COUNTERS``: the sliding
    layers under ``lm.window_attention``, the full layer under
    ``lm.attention`` (the name ``attention_ms.tune`` reads), and the counter
    ``window_tile_share`` a step beside the loss — 1.0 at 32 tokens, where a
    window of 8 keys still reads every key a row block of 32 has."""
    from videop2p_tpu.models import cohere2_moe as cm

    assert cm.SCOPES == ("lm.window_attention", "lm.attention", "lm.router",
                         "lm.experts", "lm.shared_expert", "lm.head_loss")
    assert cm.COUNTERS == ("expert_load_max_over_mean", "held_pair_share",
                           "routed_over_shared", "window_tile_share")
    metrics = _token_family_main_emits(cm, cm.Cohere2MoeConfig.tiny(),
                                       "command-a-plus-s8-tune.yaml")
    assert metrics[0]["window_tile_share"] == 1.0


def test_fourth_token_family_main_emits_the_scopes_and_counters_read():
    """The same for ``models/keye_vl2.SCOPES`` / ``COUNTERS``: the
    projections, QK-norm and rotary under ``lm.attn_proj`` (what
    ``attn_proj_ms.tune`` reads), the pair under ``lm.sparse_attention``,
    the experts under ``lm.experts`` — forward and backward — and the
    scorer's ``lm.indexer`` / ``lm.select`` in the forward only (no gradient
    flows through it); ``keys_selected_mean`` a step beside the loss: 16 keys
    of up to 32 a query at the tiny size, and the ties with the 16th."""
    import types

    from videop2p_tpu.models import keye_vl2 as ky

    assert ky.SCOPES == ("lm.attn_proj", "lm.indexer", "lm.select",
                         "lm.sparse_attention", "lm.router", "lm.experts",
                         "lm.head_loss")
    assert ky.COUNTERS == ("keys_selected_mean", "expert_load_max_over_mean",
                           "held_pair_share")
    cfg = ky.KeyeVL2Config.tiny()
    scorer = ("lm.indexer", "lm.select")
    differentiated = types.SimpleNamespace(
        SCOPES=tuple(s for s in ky.SCOPES if s not in scorer),
        COUNTERS=ky.COUNTERS, abstract_params=ky.abstract_params,
        forward_loss=ky.forward_loss)
    metrics = _token_family_main_emits(differentiated, cfg,
                                       "keye-vl2-30b-a3b-s1-tune.yaml")
    # ties with the 16th score are kept: a few keys over the count
    assert 12.25 == (16 * 17 / 2 + 16 * 16) / 32 <= metrics[0][
        "keys_selected_mean"] < 13
    assert metrics[0]["held_pair_share"] == 1.0
    params = ky.abstract_params(cfg, jnp.float32)["params"]
    text = jax.jit(jax.grad(
        lambda p, i: ky.forward_loss(p, cfg, i, jnp.float32)[0])).lower(
            params, jax.ShapeDtypeStruct((32,), jnp.int32)).as_text(
                debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in scorer:
        mine = [n for n in names if scope in n]
        assert mine and not [n for n in mine if "transpose(" in n], scope


def test_fifth_token_family_main_emits_the_scopes_and_counters_read():
    """The same for ``models/falcon_h1.SCOPES`` / ``COUNTERS``: the mixer
    under ``lm.mamba_proj`` / ``lm.ssd`` and the attention under
    ``lm.attention`` (the names the hybrid cell's readers take), the dense
    feed-forward under ``lm.dense_ffn`` (what ``dense_ffn_ms.tune`` reads),
    forward and backward; ``ssd_state_rms`` and ``branch_rms_ratio`` a step
    beside the loss, both above 0 (the state is handed on; both branches
    contribute)."""
    from videop2p_tpu.models import falcon_h1 as fh

    assert fh.SCOPES == ("lm.mamba_proj", "lm.ssd", "lm.attention",
                         "lm.dense_ffn", "lm.head_loss")
    assert fh.COUNTERS == ("ssd_state_rms", "branch_rms_ratio")
    metrics = _token_family_main_emits(fh, fh.FalconH1Config.tiny(),
                                       "falcon-h1-34b-s1-tune.yaml")
    assert metrics[0]["ssd_state_rms"] > 0
    assert 0 < metrics[0]["branch_rms_ratio"] < 10
