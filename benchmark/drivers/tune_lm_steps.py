"""Driver: Stage-1 tuning steps of a TOKEN model through
``videop2p_tpu.cli.run_tuning.main`` (``model_family: deepseek_v32``).

The same shape as ``tune_steps.py``: ONE call of ``main`` on the cell's CLI
config; it builds the model from the benchmark's seeded weights, loads the
document, compiles ``train_steps`` and runs its first call (set-up; its
output is what the check compares), and goes on calling the SAME compiled
object with the SAME state in its own loop — those calls are the window. The
driver wraps the ``instrumented_jit`` that ``main`` calls only to read the
clock, to copy what the check needs, and to end the loop by raising
``WindowClosed`` through ``main`` at the first call boundary past
``--seconds``. A traced run makes one more call after the window, under the
profiler, and reduces its device events by the program's named scopes
(``lm.*``, ``train.*``: the ``tf_op`` of each event).

What is steered, from here: the model's ``init_params`` becomes the
benchmark's generator (``--seed``), with each expert layer's selection bias
refit on the document as a checkpoint's is trained to be — BEFORE ``main``
is called, by the plain reference's float32 routing
(``weights_lm.balance_routers``; nothing of the program prepares what both
sides are fed) and put into the weights ``build_token_model`` returns;
``model`` in the CLI config is built from the benchmark's
configuration file (the published keys and the chip's share) with
``hand_out_choices`` on; the document is written by the benchmark from the
cell's ``document_seed`` (the same for every seed).

What the check compares comes from the TIMED program alone: the first
``train_steps`` call's new state and losses, and — its ``aux`` — what every
layer chose at every step (selected keys, experts a token) with each expert
layer's ``routed_over_shared``. No other forward pass of the program is
run. A parent commit without the model family fails at ``main``'s
signature, at once.
"""

from __future__ import annotations

import os
import re
import shutil
import time

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "index_n_heads", "index_head_dim", "index_topk", "n_shared_experts",
    "n_group", "topk_group", "num_experts_per_tok", "routed_scaling_factor",
    "vocab_size", "rms_norm_eps", "rope_theta", "rope_scaling",
)
_SCOPE = re.compile(r"\b(lm\.[a-z_]+|train\.[a-z_]+)")


class WindowClosed(Exception):
    pass


def model_from_config(config: dict) -> dict:
    """The program's ``model`` dict from the benchmark's configuration: the
    published keys, with the counts the file gives as HELD turned back into
    the published count plus the range held."""
    dep = config["deployment"]
    model = {k: config[k] for k in MODEL_KEYS}
    model.update(n_routed_experts=dep["n_routed_experts_published"],
                 num_attention_heads=dep["num_attention_heads_published"],
                 experts_held=list(dep["experts_held"]),
                 heads_held=list(dep["heads_held"]),
                 hand_out_choices=True)
    assert dep["experts_held"][1] == config["n_routed_experts"]
    assert dep["heads_held"][1] == config["num_attention_heads"]
    return model


def _stat_texts(buf: bytes, stat_names: dict) -> list:
    """The string values of the ``XStat`` entries among a message's fields
    (``field`` 4 of an event, 5 of an event's metadata): ``str_value``, or
    the name a ``ref_value`` points at."""
    from videop2p_tpu.obs.trace import _iter_fields

    out = []
    for f, w, v in _iter_fields(buf):
        if f == 5 and w == 2:
            out.append(v.decode("utf-8", "replace"))
        elif f == 7 and w == 0:
            out.append(stat_names.get(v, ""))
    return out


def device_events_with_scope_text(trace_dir: str) -> dict:
    """``{device plane: [(name, start_ns, dur_ns, {"text": ...})]}`` of the
    ``XLA Ops`` lines, where ``text`` joins the event's name with every
    string stat of the event AND of its metadata (on the TPU an op's
    ``tf_op`` — the JAX name stack with the program's named scopes — is a
    stat of the event's metadata, which ``jax.profiler.ProfileData`` does
    not hand out). Read with the program's own protobuf walker
    (``videop2p_tpu/obs/trace.py``)."""
    import glob

    from videop2p_tpu.obs.trace import (_iter_fields, _parse_line,
                                        _parse_metadata_entry)

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    with open(paths[-1], "rb") as f:
        data = f.read()
    out = {}
    for field, wire, plane in _iter_fields(data):
        if field != 1 or wire != 2:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for f, w, v in _iter_fields(plane):
            if f == 2 and w == 2:
                name = v.decode("utf-8", "replace")
            elif f == 3 and w == 2:
                lines.append(v)
            elif f == 4 and w == 2:
                metas.append(v)
            elif f == 5 and w == 2:
                k, n = _parse_metadata_entry(v)
                stat_names[k] = n
        if not name.startswith("/device:TPU:"):
            continue
        meta_text = {}
        for entry in metas:
            key, text = 0, []
            for f, w, v in _iter_fields(entry):
                if f == 1 and w == 0:
                    key = v
                elif f == 2 and w == 2:
                    for mf, mw, mv in _iter_fields(v):
                        if mf == 2 and mw == 2:
                            text.append(mv.decode("utf-8", "replace"))
                        elif mf == 5 and mw == 2:
                            text += _stat_texts(mv, stat_names)
            meta_text[key] = text
        for raw in lines:
            line = _parse_line(raw)
            if line["name"] != "XLA Ops":
                continue
            base_ns = int(line["timestamp_ns"])
            events = []
            for ev in line["events"]:
                text = meta_text.get(ev["metadata_id"], ["?"])
                events.append((text[0], base_ns + ev["offset_ps"] // 1000,
                               ev["duration_ps"] // 1000,
                               {"text": " ".join(text)}))
            out[name] = events
    return out


def scope_seconds(trace_dir: str, n_devices: int):
    """Device SELF seconds by the program's innermost named scope
    (``lm.*`` before ``train.*``), with the harness's own nesting of
    events. ``None`` where no event carries a scope (a program without
    them), so the metrics built on it are left out."""
    from benchmark.harness import trace

    out = {}
    for events in device_events_with_scope_text(trace_dir).values():
        for _, _, _, self_ns, _, stats in trace.self_times(events):
            found = _SCOPE.findall(stats["text"])
            if found:
                lm = [f for f in found if f.startswith("lm.")]
                key = (lm or found)[-1]
                out[key] = out.get(key, 0.0) + max(self_ns, 0) / 1e9
    return {k: v / n_devices for k, v in out.items()} or None


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import load_config
    from videop2p_tpu.models import deepseek

    from benchmark.drivers.tune_steps import _adam_state
    from benchmark.harness import steer, trace, weights_lm
    from benchmark.harness.result import device_record
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import tune_lm_check

    cell, config, note = ctx["cell"], ctx["config"], ctx["note"]
    seed32 = int(ctx["seed"]) % (2 ** 31 - 1)
    export = steer.no_export()

    cfg = load_config(os.path.join(ctx["root"], cell["cli_config"]))
    out_dir = os.path.join(ctx["out_dir"], "tune")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    model = model_from_config(config)
    assert cfg["model_family"] == "deepseek_v32"
    for k, v in cfg["model"].items():  # the YAML states the same model
        assert model[k] == v, (k, model[k], v)
    n_tokens = int(config["geometry"]["tokens"])
    if ctx["rehearse"]:
        n_tokens = int(cell["rehearse_tokens"])
    model_cfg = (deepseek.DeepSeekV32Config.tiny() if ctx["rehearse"]
                 else deepseek.DeepSeekV32Config.from_dict(model))
    doc = weights_lm.document(cell["document_seed"], n_tokens,
                              model_cfg.vocab_size)
    ids = jnp.asarray(doc)
    # the selection biases, before the program exists: the seeded weights as
    # drawn, the plain reference's float32 forward pass over the document
    weights_lm.steer_init()
    t0 = time.perf_counter()
    drawn = flatten_named(weights_lm.regenerate(seed32, model_cfg))
    biases = weights_lm.balance_routers(
        drawn, tune_lm_check.arch_for(config, ctx["rehearse"]), ids,
        None if ctx["rehearse"] else cell["reference_row_block"])
    del drawn
    steer.free_program_state()  # the pass's programs and the drawn weights
    note({"phase": "selection_biases", "s": round(time.perf_counter() - t0, 2),
          "layers": sorted(biases)})
    doc_path = os.path.join(out_dir, "document.npy")
    np.save(doc_path, doc)
    cfg.update(cell["cli_overrides"])
    cfg.update(model=model, output_dir=out_dir, seed=seed32,
               max_train_steps=10 ** 6,  # the window ends the loop, not this
               train_data={"document_path": doc_path, "n_tokens": n_tokens})
    steps_per_call = int(cfg["steps_per_call"])
    hp = config["training"]
    assert list(cfg["trainable_modules"]) == hp["trainable_modules"]
    assert float(cfg["learning_rate"]) == hp["learning_rate"]

    state = {"calls": [], "first": None, "inputs": {}, "t_start": None,
             "t_end": None, "final": None, "trace": None, "cache0": None}
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    real_jit = run_tuning.instrumented_jit
    real_build = run_tuning.build_token_model

    def build_with_biases(*a, **kw):
        # after the jitted generator, so that one init program serves every
        # seed (as constants of it the biases would compile it anew)
        bundle = real_build(*a, **kw)
        bundle.params = weights_lm.with_biases(bundle.params, biases)
        return bundle

    def wrapping_jit(fn, **kw):
        prog = real_jit(fn, **kw)  # main's own object: nothing changed
        if kw.get("program") != "train_steps":
            return prog
        note({"phase": "tracing_train_steps",
              "compile_cache": ctx["cache"].snapshot()})

        def call(s, key, n):
            return jax.block_until_ready(prog(s, key, n))

        def steps_fn(s, key, n):
            idx = len(state["calls"])
            now = time.perf_counter()
            if idx == 0:
                # copies for the check go to the HOST: beside the program
                # the device has 1.2 GiB to spare by the compiler's count
                state["inputs"]["init_trainable"] = jax.device_get(s.trainable)
            elif idx == 1:
                state["t_start"] = now
                state["cache0"] = ctx["cache"].snapshot()
            elif now - state["t_start"] >= ctx["seconds"]:
                if ctx["trace"]:
                    # the window has closed: one more call, under the
                    # profiler, for the device's side of the per-layer metrics
                    trace.start(trace_dir)
                    t_tr = time.perf_counter()
                    s = call(s, key, n)[0]
                    state["trace"] = {"dir": trace_dir, "steps": int(n),
                                      "window_s": time.perf_counter() - t_tr}
                    trace.stop()
                state["final"] = s
                raise WindowClosed()
            t1 = time.perf_counter()
            out = call(s, key, n)
            t2 = time.perf_counter()
            new, losses = out[0], np.asarray(jax.device_get(out[1]))
            state["calls"].append({"steps": int(n), "s": t2 - t1,
                                   "finite": bool(np.isfinite(losses).all()),
                                   "loss_last": float(losses[-1])})
            state["t_end"] = t2
            if idx == 0:
                adam = _adam_state(new.opt_state)
                aux = dict(out[-1])
                # what every layer chose at every step: (steps, batch, ...)
                # arrays a layer (a scalar a step is its mean over the batch
                # of one), to the host, cut into steps of document 0
                chosen = jax.device_get(aux.pop("choices"))
                state["first"] = {
                    "losses": losses,
                    "trainable": jax.device_get(new.trainable),
                    "mu": jax.device_get(adam.mu),
                    "nu": jax.device_get(adam.nu),
                    "counters": {k: float(np.mean(np.asarray(v)))
                                 for k, v in aux.items()},
                    "choices": [[{k: None if v is None else
                                  v[i] if v.ndim == 1 else v[i, 0]
                                  for k, v in layer.items()}
                                 for layer in chosen] for i in range(int(n))],
                }
                note({"phase": "first_call", "s": round(t2 - t1, 2),
                      "steps": int(n), "loss_first": float(losses[0]),
                      "counters": state["first"]["counters"],
                      "compile_cache": ctx["cache"].snapshot()})
            return out

        return steps_fn

    run_tuning.build_token_model = build_with_biases
    run_tuning.instrumented_jit = wrapping_jit
    try:
        run_tuning.main(**cfg, tiny=ctx["rehearse"],
                        ledger=os.path.join(out_dir, "ledger.jsonl"))
        raise RuntimeError("run_tuning.main returned before the window "
                           "closed")
    except WindowClosed:
        pass
    finally:
        run_tuning.build_token_model = real_build
        run_tuning.instrumented_jit = real_jit

    window_calls = state["calls"][1:]
    window_s = state["t_end"] - state["t_start"]
    setup_s = state["t_start"] - ctx["t0"]
    cache_in_window = ctx["cache"].since(state["cache0"])
    failed = sum(not c["finite"] for c in window_calls)
    if cache_in_window["writes"]:
        note({"phase": "COMPILE_IN_WINDOW", **cache_in_window})
        failed = len(window_calls)
    steps = sum(c["steps"] for c in window_calls)
    end_to_end = {"setup_s": setup_s, "tune_step_ms": 1e3 * window_s / steps}
    device = device_record(ctx["devices"])
    first = state.pop("first")
    window = {
        "kind": "tune_lm", "window_s": window_s, "steps": steps,
        "calls": window_calls, "first_call": state["calls"][0],
        "tokens": n_tokens, "batch": int(cfg.get("train_batch_size", 1)),
        "cache_in_window": cache_in_window, "export_calls": export["calls"],
        "traced_steps": state["trace"]["steps"] if state["trace"] else None,
        "memory_peak_bytes": device["memory_peak_bytes"],
        "counters": first["counters"],
    }

    final = state.pop("final")
    init_tree = state.pop("inputs").pop("init_trainable")

    # keep what the check needs by name; free the rest of the program
    frozen_prints = weights_lm.fingerprints(
        flatten_named({"params": final.frozen}))
    prog = {"losses": first["losses"], "choices": first["choices"]}
    for part in ("trainable", "mu", "nu"):
        prog[part] = flatten_named({"params": first[part]})
    trace_info = state.pop("trace")
    init_trainable = flatten_named({"params": init_tree})
    del final, first, state, init_tree
    steer.free_program_state()

    traced = None
    if trace_info:
        traced = trace.reduce(trace_info["dir"], trace_info["window_s"],
                              len(ctx["devices"]),
                              allow_empty=ctx["rehearse"])
        if traced is not None:
            traced["scope_s"] = scope_seconds(trace_info["dir"],
                                              len(ctx["devices"]))
            note({"phase": "scopes", "scope_s": traced["scope_s"]})
        shutil.rmtree(trace_info["dir"], ignore_errors=True)

    def check(**how) -> dict:
        """``how``: a planted fault or a control precision of the reference
        (the builder's readings, ``tests/read_limits_lm.py``)."""
        return tune_lm_check.run_check(
            config=config, cell=cell, seed=ctx["seed"], prog=prog,
            biases=biases, init_trainable=init_trainable, frozen_prints=frozen_prints,
            ids=ids, n_steps=steps_per_call, calls_not_finite=failed,
            rehearse=ctx["rehearse"], note=note, **how)

    return {
        "attempted": len(window_calls), "failed": failed,
        "end_to_end": end_to_end, "window": window, "device": device,
        "trace": traced, "check": check,
        "summary": {"window_s": round(window_s, 3), "steps": steps,
                    "setup_s": round(setup_s, 2), "failed": failed,
                    "cache_in_window": cache_in_window,
                    "call_s": [round(c["s"], 3) for c in window_calls],
                    "first_call_s": round(window["first_call"]["s"], 2)},
    }
