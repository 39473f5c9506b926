"""Driver: Stage-1 tuning steps of the THIRD token family through
``videop2p_tpu.cli.run_tuning.main`` (``model_family: cohere2_moe``).

The shape of ``tune_hybrid_steps.py`` (the window exception and the scope
reducer are ``tune_lm_steps.py``'s, imported): ONE call of ``main`` on the
cell's CLI config; it builds the model from the benchmark's seeded weights,
loads the document, compiles ``train_steps`` and runs its first call
(set-up; its output is what the check compares), and goes on calling the
SAME compiled object with the SAME state in its own loop — those calls are
the window. The driver wraps the ``instrumented_jit`` that ``main`` calls
only to read the clock, to copy what the check needs, and to end the loop
by raising ``WindowClosed`` through ``main`` at the first call boundary past
``--seconds``. A traced run makes one more call after the window, under the
profiler, and reduces its device events by the program's named scopes
(``lm.*``, ``train.*``), and — for the windowed kernel pair's roofline — the
device time of the attention kernels' own events under
``lm.window_attention``.

What is steered, from here: the model's ``init_params`` becomes the
benchmark's generator (``--seed``; ``weights_command.py``), with each
layer's router rows made orthogonal to what the tokens of a stretch of the
document share and then rescaled, so that the loads are level —
BEFORE ``main`` is called, by the plain reference's float32 layers
(``weights_command.level_router_rows``; nothing of the program prepares
what both sides are fed; the pass is the reference's time, so its seconds
are taken out of ``setup_s`` and given as the summary's ``router_rows_s``)
and put into the weights ``build_token_model`` returns; ``model`` in the CLI
config is built from the benchmark's configuration file (the published keys
and the chip's share) with ``hand_out_choices`` on; the document is written
by the benchmark from the cell's ``document_seed`` (the same for every
seed).

What the check compares comes from the TIMED program alone: the first
``train_steps`` call's new state and losses, and — its ``aux`` — the experts
every layer chose for every token at every step with each layer's
``routed_over_shared``. No other forward pass of the program is run. A
parent commit without the model family fails at ``check_model_family``, at
once, with an error that names the family.
"""

from __future__ import annotations

import os
import shutil
import time

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "head_dim", "num_hidden_layers",
    "layer_types", "sliding_window", "rope_theta", "rotary_pct",
    "position_embedding_type", "num_experts_per_tok", "expert_selection_fn",
    "norm_topk_prob", "shared_expert_combination_strategy",
    "first_k_dense_replace", "use_parallel_block", "use_gated_activation",
    "use_qk_norm", "attention_bias", "hidden_act", "layer_norm_eps",
    "logit_scale", "tie_word_embeddings", "vocab_size",
)
HELD = (("num_experts", "experts_held"),
        ("num_attention_heads", "heads_held"))
_KERNEL_PAIR = "lm_selected_attention"  # and its ``_bwd``


def model_from_config(config: dict) -> dict:
    """The program's ``model`` dict from the benchmark's configuration: the
    published keys, with the counts the file gives as HELD turned back into
    the published count plus the range held (the key / value heads follow
    from the query heads' range)."""
    dep = config["deployment"]
    model = {k: config[k] for k in MODEL_KEYS}
    for key, held in HELD:
        model[key] = dep[key + "_published"]
        model[held] = list(dep[held])
        assert dep[held][1] == config[key], key
    model["num_key_value_heads"] = dep["num_key_value_heads_published"]
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    assert [v // group for v in dep["heads_held"]] == list(dep["kv_heads_held"])
    assert dep["kv_heads_held"][1] == config["num_key_value_heads"]
    # the shared experts: the published count, and the inner columns held
    model["num_shared_experts"] = dep["num_shared_experts_published"]
    model["shared_columns_held"] = list(dep["shared_columns_held"])
    assert (dep["shared_columns_published"]
            == model["num_shared_experts"] * config["intermediate_size"])
    assert (dep["shared_columns_held"][1] == config["num_shared_experts"]
            * config["intermediate_size"])
    model["hand_out_choices"] = True
    return model


def kernel_seconds(trace_dir: str, n_devices: int) -> dict:
    """``{scope: device SELF seconds}`` of the attention kernel pair's own
    events by their innermost ``lm.`` scope: the roofline's denominator is
    ``lm.window_attention``'s. Empty where no such event is in the trace (a
    program without the scopes, or one that attends as XLA)."""
    from benchmark.drivers.tune_lm_steps import (_SCOPE,
                                                 device_events_with_scope_text)
    from benchmark.harness import trace

    out = {}
    for events in device_events_with_scope_text(trace_dir).values():
        for _, _, _, self_ns, _, stats in trace.self_times(events):
            lm = [f for f in _SCOPE.findall(stats["text"])
                  if f.startswith("lm.")]
            if lm and _KERNEL_PAIR in stats["text"]:
                out[lm[-1]] = out.get(lm[-1], 0.0) + max(self_ns, 0) / 1e9
    return {k: v / n_devices for k, v in out.items()}


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import check_model_family, load_config

    cell, config, note = ctx["cell"], ctx["config"], ctx["note"]
    # a program without the family stops HERE, before anything is built
    check_model_family(config["model_type"])
    cfg = load_config(os.path.join(ctx["root"], cell["cli_config"]))
    assert cfg["model_family"] == config["model_type"]

    from videop2p_tpu.models import cohere2_moe

    from benchmark.drivers.tune_lm_steps import WindowClosed, scope_seconds
    from benchmark.drivers.tune_steps import _adam_state
    from benchmark.harness import steer, trace, weights_command, weights_lm
    from benchmark.harness.result import device_record
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import tune_command_check

    seed32 = int(ctx["seed"]) % (2 ** 31 - 1)
    export = steer.no_export()
    out_dir = os.path.join(ctx["out_dir"], "tune")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    model = model_from_config(config)
    for k, v in cfg["model"].items():  # the YAML states the same model
        assert model[k] == v, (k, model[k], v)
    n_tokens = int(config["geometry"]["tokens"])
    if ctx["rehearse"]:
        n_tokens = int(cell["rehearse_tokens"])
    model_cfg = (cohere2_moe.Cohere2MoeConfig.tiny() if ctx["rehearse"]
                 else cohere2_moe.Cohere2MoeConfig.from_dict(
                     {k: v for k, v in model.items() if k != "hand_out_choices"}))
    doc = weights_lm.document(cell["document_seed"], n_tokens,
                              model_cfg.vocab_size)
    ids = jnp.asarray(doc)
    # the router's rows, before the program exists: the seeded weights as
    # drawn, the plain reference's float32 forward pass over the document
    weights_command.steer_init()
    t0 = time.perf_counter()
    drawn = flatten_named(weights_command.regenerate(seed32, model_cfg))
    rows = weights_command.level_router_rows(
        drawn, tune_command_check.arch_for(config, ctx["rehearse"]), ids,
        None if ctx["rehearse"] else cell["reference_row_block"])
    del drawn
    steer.free_program_state()  # the pass's programs and the drawn weights
    # the plain reference's own seconds: noted, and not part of ``setup_s``
    rows_s = time.perf_counter() - t0
    note({"phase": "router_rows", "s": round(rows_s, 2)})
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

    def build_with_rows(*a, **kw):
        # after the jitted generator, so that one init program serves every
        # seed (as constants of it the rows would compile it anew)
        bundle = real_build(*a, **kw)
        bundle.params = weights_command.with_router_kernels(bundle.params,
                                                            rows)
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

    run_tuning.build_token_model = build_with_rows
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
    setup_s = state["t_start"] - ctx["t0"] - rows_s
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
        "kind": "tune_command", "window_s": window_s, "steps": steps,
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
            traced["attention_kernel_s"] = kernel_seconds(
                trace_info["dir"], len(ctx["devices"]))
            note({"phase": "scopes", "scope_s": traced["scope_s"],
                  "attention_kernel_s": traced["attention_kernel_s"]})
        shutil.rmtree(trace_info["dir"], ignore_errors=True)

    def check(**how) -> dict:
        """``how``: a planted fault or a control precision of the reference
        (the builder's readings, ``tests/read_limits_command.py``)."""
        return tune_command_check.run_check(
            config=config, cell=cell, seed=ctx["seed"], prog=prog, rows=rows,
            init_trainable=init_trainable, frozen_prints=frozen_prints,
            ids=ids, n_steps=steps_per_call, calls_not_finite=failed,
            rehearse=ctx["rehearse"], note=note, **how)

    return {
        "attempted": len(window_calls), "failed": failed,
        "end_to_end": end_to_end, "window": window, "device": device,
        "trace": traced, "check": check,
        "summary": {"window_s": round(window_s, 3), "steps": steps,
                    "setup_s": round(setup_s, 2),
                    "router_rows_s": round(rows_s, 2), "failed": failed,
                    "cache_in_window": cache_in_window,
                    "call_s": [round(c["s"], 3) for c in window_calls],
                    "first_call_s": round(window["first_call"]["s"], 2)},
    }
