"""Driver: Stage-1 tuning steps through ``videop2p_tpu.cli.run_tuning.main``.

ONE call of ``main`` on the cell's CLI config: it builds the model from the
benchmark's seeded weights, encodes the clip, compiles ``train_steps`` and
runs its first call (set-up; its output is what the check compares), and
goes on calling the SAME compiled object with the SAME state in its own
loop — those calls are the window. A traced run makes one more call after
the window has closed, under the profiler. The timed program is ``main``'s
own jitted object, unchanged: the driver sits between ``main`` and it (it
wraps the ``instrumented_jit`` that ``main`` calls) only to read the clock,
to copy what the check needs, and to end the loop: once ``--seconds`` have
passed at a call boundary it raises ``WindowClosed`` through ``main``. So the
number of measured steps is a whole number of calls worked out at run time.
``main``'s final export (3.4 GB of float32 on disk) is never reached;
``steer.no_export`` guards it all the same.

``main`` bakes the clip's latents and text states into ``train_steps`` as
constants, so a clip that differs compiles it anew (five minutes here). The
cell therefore keeps the clip the same for every seed: the VAE and the text
encoder are seeded by the cell's ``weights_seed`` and the CLI's own seed
(the encoder's sampling key and the noise key) is the cell's ``cli_seed``;
``--seed`` seeds the UNet, which enters the program as an argument."""

from __future__ import annotations

import os
import shutil
import time


class WindowClosed(Exception):
    pass


def _adam_state(opt_state):
    import jax

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    assert len(found) == 1, "one Adam state expected in the optimizer state"
    return found[0]


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from videop2p_tpu.cli import run_tuning
    from videop2p_tpu.cli.common import load_config

    from benchmark.harness import steer, trace
    from benchmark.harness.result import device_record
    from benchmark.harness.weights import flatten_named

    cell, config, note = ctx["cell"], ctx["config"], ctx["note"]
    seed32 = int(ctx["seed"]) % (2 ** 31 - 1)
    if not ctx["rehearse"]:
        steer.cut_depth(config)
    fixed = int(cell["weights_seed"])
    steer.seeded_weights(fixed={"vae": fixed, "text": fixed})
    export = steer.no_export()

    cfg = load_config(os.path.join(ctx["root"], cell["cli_config"]))
    out_dir = os.path.join(ctx["out_dir"], "tune")
    shutil.rmtree(out_dir, ignore_errors=True)
    cfg["output_dir"] = out_dir
    cfg["train_data"]["video_path"] = os.path.join(ctx["root"], cell["clip"])
    cfg["validation_data"].update(cell["validation_overrides"])
    cfg.update(cell["cli_overrides"])
    cfg["seed"] = int(cell["cli_seed"])
    cfg["max_train_steps"] = 10 ** 6  # the window ends the loop, not this
    if ctx["rehearse"]:
        cfg["train_data"].update(cell["rehearse_train_data"])
        spc = int(cell["rehearse_steps_per_call"])
        cfg.update(steps_per_call=spc, log_every=spc)
    steps_per_call = int(cfg["steps_per_call"])
    hp = config["training"]
    assert list(cfg["trainable_modules"]) == hp["trainable_modules"]
    assert float(cfg["learning_rate"]) == hp["learning_rate"]

    state = {"calls": [], "first": None, "inputs": {}, "t_start": None,
             "t_end": None, "final": None, "trace": None, "cache0": None}
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    real_train_steps = run_tuning.train_steps
    real_jit = run_tuning.instrumented_jit

    real_build = run_tuning.build_models

    def build_with_seed(*a, **kw):
        # the UNet's weights come from --seed (the VAE and the text encoder
        # keep the cell's own, see ``fixed`` above)
        return real_build(*a, **{**kw, "seed": seed32})

    def capturing_train_steps(unet_fn, tx, s, sched, latents, text, k, **kw):
        # called while ``main``'s lambda is traced: the clip's latents and
        # text states pass through as they are; the check keeps them
        state["inputs"].update(latents=latents, text=text)
        return real_train_steps(unet_fn, tx, s, sched, latents, text, k, **kw)

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
                state["inputs"]["run_key"] = key
                state["inputs"]["init_trainable"] = jax.tree.map(
                    jnp.copy, s.trainable)
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
            new, losses = out[0], out[1]
            losses = np.asarray(jax.device_get(losses))
            state["calls"].append({"steps": int(n), "s": t2 - t1,
                                   "finite": bool(np.isfinite(losses).all()),
                                   "loss_last": float(losses[-1])})
            state["t_end"] = t2
            if idx == 0:
                adam = _adam_state(new.opt_state)
                state["first"] = {
                    "losses": losses,
                    "trainable": jax.tree.map(jnp.copy, new.trainable),
                    "mu": jax.tree.map(jnp.copy, adam.mu),
                    "nu": jax.tree.map(jnp.copy, adam.nu),
                }
                note({"phase": "first_call", "s": round(t2 - t1, 2),
                      "steps": int(n), "loss_first": float(losses[0]),
                      "compile_cache": ctx["cache"].snapshot()})
            return out

        return steps_fn

    run_tuning.build_models = build_with_seed
    run_tuning.train_steps = capturing_train_steps
    run_tuning.instrumented_jit = wrapping_jit
    try:
        run_tuning.main(**cfg, tiny=ctx["rehearse"],
                        ledger=os.path.join(out_dir, "ledger.jsonl"))
        raise RuntimeError("run_tuning.main returned before the window "
                           "closed")
    except WindowClosed:
        pass
    finally:
        run_tuning.build_models = real_build
        run_tuning.train_steps = real_train_steps
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
    window = {
        "kind": "tune", "window_s": window_s, "steps": steps,
        "calls": window_calls, "first_call": state["calls"][0],
        "frames": int(cfg["train_data"]["n_sample_frames"]),
        "batch": int(cfg.get("train_batch_size", 1)),
        "cache_in_window": cache_in_window, "export_calls": export["calls"],
        "traced_steps": state["trace"]["steps"] if state["trace"] else None,
        "memory_peak_bytes": device["memory_peak_bytes"],
    }

    # keep what the check needs by name; free the rest of the program
    final = state.pop("final")
    frozen_final = flatten_named({"params": final.frozen})
    first = state.pop("first")
    prog = {"losses": first["losses"]}
    for part in ("trainable", "mu", "nu"):
        prog[part] = flatten_named({"params": first[part]})
    inputs = state.pop("inputs")
    trace_info = state.pop("trace")
    init_trainable = flatten_named({"params": inputs.pop("init_trainable")})
    del final, first, state
    steer.free_program_state()

    traced = None
    if trace_info:
        traced = trace.reduce(trace_info["dir"], trace_info["window_s"],
                              len(ctx["devices"]),
                              allow_empty=ctx["rehearse"])
        shutil.rmtree(trace_info["dir"], ignore_errors=True)

    def check() -> dict:
        from benchmark.reference import tune_check

        return tune_check.run_check(
            config=config, cell=cell, seed=ctx["seed"], prog=prog,
            init_trainable=init_trainable, frozen_final=frozen_final,
            latents=inputs["latents"], text=inputs["text"],
            run_key=inputs["run_key"], n_steps=steps_per_call,
            calls_not_finite=failed, rehearse=ctx["rehearse"], note=note)

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
