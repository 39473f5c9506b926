"""Driver: Stage-1 tuning steps of the FIFTH token family through
``videop2p_tpu.cli.run_tuning.main`` (``model_family: falcon_h1``).

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
(``lm.*``, ``train.*``).

What is steered, from here: the model's ``init_params`` becomes the
benchmark's generator (``--seed``; ``weights_falcon.py``); ``model`` in the
CLI config is built from the benchmark's configuration file (the published
keys, the layers and vocabulary rows HELD); the document is written by the
benchmark from the cell's ``document_seed`` (the same for every seed).
Nothing is fitted to the document: the block has no router.

What the check compares comes from the TIMED program alone: the first
``train_steps`` call's new state and losses, and its first step's counters
``ssd_state_rms`` and ``branch_rms_ratio``. No other forward pass of the
program is run. A parent commit without the model family fails at
``check_model_family``, at once, with an error that names the family.
"""

from __future__ import annotations

import os
import shutil
import time

FAMILY = "falcon_h1"  # the published model_type
MODEL_KEYS = (
    "hidden_size", "intermediate_size", "mlp_expansion_factor",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rope_theta", "rope_scaling", "max_position_embeddings",
    "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
    "embedding_multiplier", "lm_head_multiplier", "mlp_multipliers",
    "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
    "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv", "mamba_d_head",
    "mamba_d_ssm", "mamba_d_state", "mamba_expand", "mamba_n_groups",
    "mamba_n_heads", "mamba_norm_before_gate", "mamba_proj_bias",
    "mamba_rms_norm", "mamba_use_mlp", "attention_bias", "mlp_bias",
    "projectors_bias", "hidden_act", "attn_layer_indices",
    "num_logits_to_keep", "rms_norm_eps", "tie_word_embeddings",
    "vocab_size",
)


def model_from_config(config: dict) -> dict:
    """The program's ``model`` dict from the benchmark's configuration: the
    published keys (the layers and the vocabulary rows HELD)."""
    return {k: config[k] for k in MODEL_KEYS}


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import check_model_family, load_config

    cell, config, note = ctx["cell"], ctx["config"], ctx["note"]
    # a program without the family stops HERE, before anything is built
    check_model_family(FAMILY)
    cfg = load_config(os.path.join(ctx["root"], cell["cli_config"]))
    assert (cfg["model_family"], config["model_type"]) == (FAMILY, FAMILY)

    from benchmark.drivers.tune_lm_steps import WindowClosed, scope_seconds
    from benchmark.drivers.tune_steps import _adam_state
    from benchmark.harness import steer, trace, weights_falcon, weights_lm
    from benchmark.harness.result import device_record
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import tune_falcon_check

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
    from videop2p_tpu.models import falcon_h1

    model_cfg = (falcon_h1.FalconH1Config.tiny() if ctx["rehearse"]
                 else falcon_h1.FalconH1Config.from_dict(model))
    weights_falcon.steer_init()
    doc = weights_lm.document(cell["document_seed"], n_tokens,
                              model_cfg.vocab_size)
    ids = jnp.asarray(doc)
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
                # copies for the check go to the HOST: the device is the
                # program's until the window closes, then the reference's
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
                # (steps, ...) a counter: the call's mean, and its first step
                aux = {k: np.asarray(jax.device_get(v)).reshape(int(n), -1)
                       for k, v in dict(out[-1]).items()}
                state["first"] = {
                    "losses": losses,
                    "trainable": jax.device_get(new.trainable),
                    "mu": jax.device_get(adam.mu),
                    "nu": jax.device_get(adam.nu),
                    "counters": {k: float(v.mean()) for k, v in aux.items()},
                    "counters_first": {k: float(v[0].mean())
                                       for k, v in aux.items()},
                }
                note({"phase": "first_call", "s": round(t2 - t1, 2),
                      "steps": int(n), "loss_first": float(losses[0]),
                      "counters": state["first"]["counters"],
                      "compile_cache": ctx["cache"].snapshot()})
            return out

        return steps_fn

    run_tuning.instrumented_jit = wrapping_jit
    try:
        run_tuning.main(**cfg, tiny=ctx["rehearse"],
                        ledger=os.path.join(out_dir, "ledger.jsonl"))
        raise RuntimeError("run_tuning.main returned before the window "
                           "closed")
    except WindowClosed:
        pass
    finally:
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
        "kind": "tune_falcon", "window_s": window_s, "steps": steps,
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
    prog = {"losses": first["losses"],
            "counters_first": first["counters_first"]}
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
        (the readings behind the limits, ``tests/read_limits_falcon.py``)."""
        return tune_falcon_check.run_check(
            config=config, cell=cell, seed=ctx["seed"], prog=prog,
            init_trainable=init_trainable, frozen_prints=frozen_prints,
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
