#!/usr/bin/env python3
"""The builder's readings for a tuning cell's limits, in ONE process on the
chip (a run's set-up is minutes, a seed's reading is one more call):

    python benchmark/tests/read_limits.py --workload sd15-tune-8f.steps \
        --seeds 11,12,13,14 --faults 3 [--control float8_e4m3fn] --seconds 20

The first seed is a whole run of the cell through its driver (set-up, window,
check). Before that run's own first call, ``main``'s own compiled
``train_steps`` is also called once from fresh weights for every further
seed; after the window has closed and the program is freed, the reference
follows each seed, and for the first ``--faults`` of the further seeds also
with half of the clip left out of the loss (and, with ``--control``, in that
precision). Every reading goes through the harness's own ``gaps``,
``compared`` and ``verdict``, one JSON line each on stdout. Not run by a
check."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    prepared = bench_run.prepare(argparse.Namespace(
        workload=args.workload, seed=seeds[0], seconds=args.seconds, trace=0,
        rehearse=args.rehearse))
    if isinstance(prepared, int):
        return prepared
    _, _, driver, ctx = prepared
    cell, config, note = ctx["cell"], ctx["config"], ctx["note"]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from videop2p_tpu.cli import run_tuning

    from benchmark.harness import steer
    from benchmark.harness.result import verdict
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import train, tune_check
    from benchmark.reference.numerics import Numerics

    extra, inputs = {}, {}
    real_jit, real_steps = run_tuning.instrumented_jit, run_tuning.train_steps

    def host(tree) -> dict:
        return {k: np.asarray(v) for k, v in flatten_named(
            {"params": jax.device_get(tree)}).items()}

    def state_for(like, seed):
        """A fresh TrainState for ``seed``: the benchmark's weights split as
        the program's own state ``like`` is, zero moments, step 0 — every
        leaf of ``like``'s type (a weak-typed step stays weak-typed), so the
        compiled program takes it as it takes ``like``."""
        named = flatten_named(steer.regenerate("unet", seed))

        def fill(part):
            treedef = jax.tree_util.tree_structure(part)
            return jax.tree_util.tree_unflatten(
                treedef, [named[n] for n in flatten_named({"params": part})])

        trainable = fill(like.trainable)
        fresh = like.replace(
            step=jnp.zeros_like(like.step), trainable=trainable,
            frozen=fill(like.frozen),
            opt_state=jax.tree.map(jnp.zeros_like, like.opt_state))
        return fresh, host(trainable)

    def keeping_steps(unet_fn, tx, s, sched, latents, text, k, **kw):
        inputs.update(latents=latents, text=text)
        return real_steps(unet_fn, tx, s, sched, latents, text, k, **kw)

    def holding_jit(fn, **kw):
        prog = real_jit(fn, **kw)
        if kw.get("program") != "train_steps":
            return prog
        seen = {"first": True}

        def proxy(s, key, n):
            if seen.pop("first", False):
                inputs["run_key"] = key
                before = ctx["cache"].snapshot()
                for seed in seeds[1:]:
                    s2, init = state_for(s, seed)
                    out = jax.block_until_ready(prog(s2, key, n))
                    adam = driver._adam_state(out[0].opt_state)
                    extra[seed] = {
                        "init": init,
                        "prog": {"losses": np.asarray(out[1]),
                                 "trainable": host(out[0].trainable),
                                 "mu": host(adam.mu), "nu": host(adam.nu)}}
                    note({"phase": "extra_seed_call", "seed": seed,
                          "loss_first": float(
                              extra[seed]["prog"]["losses"][0]),
                          "compile_cache_since_first": ctx["cache"].since(
                              before)})
                    del s2, out, adam
            return prog(s, key, n)

        return proxy

    run_tuning.instrumented_jit = holding_jit
    run_tuning.train_steps = keeping_steps
    try:
        run = driver.run(ctx)
    finally:
        run_tuning.instrumented_jit = real_jit
        run_tuning.train_steps = real_steps
    note({"phase": "window_closed", **run["summary"], **run["end_to_end"],
          "memory_peak_bytes": run["device"]["memory_peak_bytes"]})

    def say(kind, seed, cmp, g=None):
        """``g``: every number ``gaps`` computes, compared or not."""
        print(json.dumps({
            "reading": kind, "seed": seed, "correct": verdict(cmp),
            "compared": cmp,
            "gaps": {k: v for k, v in (g or {}).items() if k[0] != "_"}}),
            flush=True)

    say("program", seeds[0], run["check"]())
    arch = tune_check.arch_for(config, args.rehearse)
    hp = config["training"]
    frames = inputs["latents"].shape[1]
    weight = [1.0] * (frames // 2) + [0.0] * (frames - frames // 2)
    feed = (inputs["latents"], inputs["text"], inputs["run_key"])
    for at, seed in enumerate(seeds[1:]):
        flat = tune_check.reference_weights(seed)
        got = extra.pop(seed)
        for k, v in got["init"].items():
            assert np.array_equal(np.asarray(flat[k]), v), k
        n_steps = len(got["prog"]["losses"])
        ref = train.tune(flat, arch, hp, *feed, n_steps)
        g = tune_check.gaps(got["prog"], ref, got["init"])
        say("program", seed,
            tune_check.compared(cell, g, 0, 0, args.rehearse), g)
        # the planted fault and the control: the reference put in the
        # program's place, through the same comparison
        if at < args.faults:
            half = train.tune(flat, arch, hp, *feed, n_steps,
                              frame_weight=weight)
            g = tune_check.gaps(half, ref, got["init"])
            say("fault_half_of_the_clip", seed,
                tune_check.compared(cell, g, 0, 0, args.rehearse), g)
            del half
        if at < args.faults and args.control:
            ctl = train.tune(flat, arch, hp, *feed, n_steps,
                             nx=Numerics(args.control))
            g = tune_check.gaps(ctl, ref, got["init"])
            say("control_" + args.control, seed,
                tune_check.compared(cell, g, 0, 0, args.rehearse), g)
            del ctl
        del flat, ref, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
