"""Capture XLA cost/memory analyses of the bench programs ON CPU.

Usage:  python tools/cpu_cost_capture.py [--frames 8] [--steps 50] [--tiny]
            [--programs invert_captured,edit_cached,e2e_cached]
            [--frame_counts 8,32,64] [--shards 8] [--ledger PATH]

Besides the UNet pipeline programs, the tool builds the DISTRIBUTED unit
programs (ISSUE 10): ``ring_unit_{serial,overlap,bidir}_f<F>`` — the
standalone ring-attention pass at ``F`` frames over ``--shards`` virtual
devices, whose unrolled rotation loop makes the static collective-permute
counts TRUE per-pass counts (serial 2n / overlap 2(n−1) / bidir 4(n−1) at
half payload) — and ``tp_unit_{gspmd,scatter}`` — the Megatron
row-parallel output projection, declarative all-reduce vs the explicit
``psum_scatter`` seam. Their records merge the comm accounting
(``obs/comm.py`` collective counts/bytes) into the cost analysis, so
per-frame-count comm+flop evidence lands in ``bench_details.json`` even
on ``backend_unavailable`` rounds (``bench.record_frame_scaling``).

The PER-CALL cost units (ISSUE 15): ``unet_unit_{fp,w8,w8a8}`` — one UNet
forward at the cached edit's 2-stream batch with full-precision, int8
weight-quantized, and weight+activation-quantized parameters (the w8 tree
comes from ``jax.eval_shape`` over the real ``quantize_unet_params``
converter, so the 1-byte weights ARE the analyzed program's inputs and the
argument-bytes delta is the weight-footprint claim) — and
``reuse_unit_<K>`` — one straight-line DeepCache block (a capture forward
+ K−1 shallow forwards, loop-free so the static flop count is the true
K-step count; a ``lax.cond``'s static analysis would count BOTH branches).
``bench.per_call_cost_records`` turns these into the quantization/reuse
evidence rows.

The STUDENT cost units (ISSUE 16): ``distill_unit_fp`` — one few-step
student forward (the UNet forward plus the consistency-distilled
time-conditioning head, ``train/distill.apply_time_head``), whose flop
delta over ``unet_unit_fp`` IS the head's overhead claim — and
``distill_unit_<N>`` — N loop-free student forwards (each step with its
own abstract latent/timestep, same CSE hazard as the reuse units), the
true N-step student program a ``student:N+...`` frontier row runs. Their
ratios against the teacher units land in ``bench_details.json`` every
round, ``backend_unavailable`` included.

Builds the bench's headline programs (the captured inversion, the cached
2-stream edit, and the fused e2e — the same pipeline calls
``bench.build_fast_edit_working_point`` jits) against ABSTRACT inputs
(``jax.eval_shape`` parameters — nothing is initialized or executed),
compiles them on the CPU backend, and prints one JSON line per program:
``{"program": ..., "flops": ..., "temp_bytes": ..., "peak_hbm_bytes": ...,
"hlo_fingerprint": ..., ...}`` (obs/introspect.py's record, plus the
working-point config).

This is bench.py's backend-down fallback (VERDICT r5 "What's missing" #1:
a dead TPU left the round with ``value: null`` and nothing else): XLA's
analyses are deterministic and backend-compile on CPU needs no healthy
accelerator, so FLOPs / bytes-accessed / temp-HBM per program can be
recorded EVERY round. Lines flush as each program completes, so a caller's
timeout keeps whatever finished. ``--tiny`` swaps in the tiny UNet config
(seconds, used by the tests); ``--ledger`` additionally appends the
records as ``program_analysis`` events to a run-ledger JSONL.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import jax  # noqa: E402

# pin the CPU through jax.config too: a config value set earlier in the
# process beats JAX_PLATFORMS (as in tests/conftest.py)
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from videop2p_tpu.cli.common import enable_compile_cache  # noqa: E402

# binary boundary: persist the (CPU) compiles so a re-run after a caller
# timeout resumes warm instead of repaying minutes of XLA compile
enable_compile_cache()


def build_abstract_programs(frames: int, steps: int, tiny: bool,
                            reuse_ks=(), distill_ns=()):
    """(name → (jitted, abstract_args)) for the bench working point, with
    every array an eval_shape/ShapeDtypeStruct — no device execution.

    ``reuse_ks``: extra ``reuse_unit_<K>`` straight-line DeepCache programs
    to build (one capture forward + K−1 shallow forwards, loop-free — the
    only form whose STATIC cost counts are true per-K-step counts, since
    ``cost_analysis`` counts a ``lax.cond``'s BOTH branches and a scan body
    once).

    ``distill_ns``: extra ``distill_unit_<N>`` straight-line few-step
    student programs (N UNet-forward + time-head steps, loop-free with
    per-step abstract inputs for the same CSE reason)."""
    from videop2p_tpu.control import make_controller
    from videop2p_tpu.core import DDIMScheduler
    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.pipelines import (
        cached_fast_edit,
        ddim_inversion_captured,
        edit_sample,
        make_unet_fn,
    )
    from videop2p_tpu.pipelines.cached import capture_windows
    from videop2p_tpu.utils.tokenizers import WordTokenizer

    # the bench's model configuration, minus accelerator-only kernels: the
    # fused Pallas GroupNorm / frame-attention cannot lower for CPU, and
    # the XLA paths compute the same math (cost analysis differs only by
    # the kernel's internal schedule, which CPU could not predict anyway)
    if tiny:
        cfg = UNet3DConfig.tiny()
        lat = cfg.sample_size
        ctx_dim = cfg.cross_attention_dim
    else:
        cfg = UNet3DConfig.sd15(frame_attention="chunked", group_norm="xla")
        lat, ctx_dim = 64, 768
    model = UNet3DConditionModel(config=cfg, dtype=jnp.bfloat16)
    fn = make_unet_fn(model)
    sched = DDIMScheduler.create_sd()

    x0 = jax.ShapeDtypeStruct((1, frames, lat, lat, 4), jnp.bfloat16)
    cond = jax.ShapeDtypeStruct((2, 77, ctx_dim), jnp.bfloat16)
    cond_src = jax.ShapeDtypeStruct((1, 77, ctx_dim), jnp.bfloat16)
    uncond = jax.ShapeDtypeStruct((77, ctx_dim), jnp.bfloat16)
    params = jax.eval_shape(
        model.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 2, lat, lat, 4), jnp.bfloat16),
        jax.ShapeDtypeStruct((), jnp.int32), cond_src,
    )

    # the bench's controller working point (refine + reweight + LocalBlend)
    ctx = make_controller(
        ["a rabbit is jumping on the grass",
         "a origami rabbit is jumping on the grass"],
        WordTokenizer(),
        num_steps=steps,
        is_replace_controller=False,
        cross_replace_steps=0.2,
        self_replace_steps=0.5,
        blend_words=(["rabbit"], ["rabbit"]),
        equalizer_params={"words": ["origami"], "values": [2.0]},
    )
    cross_len, self_window = capture_windows(ctx, steps)

    invert_captured = jax.jit(
        lambda p, x, c: ddim_inversion_captured(
            fn, p, sched, x, c, num_inference_steps=steps,
            cross_len=cross_len, self_window=self_window, capture_blend=True,
        )
    )
    traj_sds, cached_sds = jax.eval_shape(
        invert_captured, params, x0, cond_src
    )
    edit_cached = jax.jit(
        lambda p, xt, c2, u, cch: edit_sample(
            fn, p, sched, xt, c2, u,
            num_inference_steps=steps, ctx=ctx, source_uses_cfg=False,
            cached_source=cch,
        )
    )
    e2e_cached = jax.jit(
        lambda p, x, c1, c2, u: cached_fast_edit(
            fn, p, sched, x, c1, c2, u, ctx,
            num_inference_steps=steps,
            cross_len=cross_len, self_window=self_window,
        )[1]
    )
    xt_sds = jax.ShapeDtypeStruct(x0.shape, x0.dtype)

    # straight-line null-text UNIT programs (bench.null_text_flop_records):
    # one UNet forward and one inner Adam iteration (loss forward + backward
    # + update). NO loops — XLA's static cost_analysis counts scan/while
    # bodies once, so only loop-free programs have static counts equal to
    # their true flops; the per-mode totals (optimize / amortized / hybrid)
    # follow analytically from these units and the disclosed loop structure.
    # The grad program uses the SAME per-block remat the real null-text
    # optimization runs with (its recompute flops are part of the real cost).
    import optax

    if tiny:
        cfg_r = type(cfg)(**{**cfg.__dict__, "gradient_checkpointing": True})
    else:
        cfg_r = UNet3DConfig.sd15(frame_attention="chunked", group_norm="xla",
                                  gradient_checkpointing=True)
    fn_r = make_unet_fn(UNet3DConditionModel(config=cfg_r, dtype=jnp.bfloat16))

    lat_f32 = jax.ShapeDtypeStruct((1, frames, lat, lat, 4), jnp.float32)
    t_sds = jax.ShapeDtypeStruct((), jnp.int32)
    u_sds = jax.ShapeDtypeStruct((1, 77, ctx_dim), jnp.float32)
    adam = optax.adam(1.0)

    def unit_fwd(p, x, t, text):
        eps, _ = fn_r(p, x, t, text, None)
        return eps.astype(jnp.float32)

    def unit_inner(p, u, lat_cur, t, eps_cond, latent_prev):
        opt_state = adam.init(u)

        def loss_fn(u_):
            eps_u, _ = fn_r(p, lat_cur, t, u_, None)
            eps = eps_u.astype(jnp.float32) + 7.5 * (
                eps_cond - eps_u.astype(jnp.float32)
            )
            prev_rec = sched.prev_step(eps, t, lat_cur, steps)
            return jnp.mean((prev_rec - latent_prev) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(u)
        updates, opt_state = adam.update(grads, opt_state, u)
        return optax.apply_updates(u, updates), loss

    # per-call cost UNIT programs (ISSUE 15, bench.per_call_cost_records):
    # ONE UNet forward at the cached edit's batch geometry (2 streams:
    # edit uncond + edit cond) in each quantization mode. The quantized
    # trees come from jax.eval_shape over the REAL load-time converter
    # (models/convert.quantize_unet_params), so the analyzed programs take
    # the actual 1-byte weight tensors as inputs — argument_bytes IS the
    # weight-footprint evidence.
    from videop2p_tpu.models.quant import fake_quant_act
    from videop2p_tpu.models.convert import quantize_unet_params

    xt_unit = jax.ShapeDtypeStruct((2, frames, lat, lat, 4), jnp.bfloat16)
    params_w8 = jax.eval_shape(
        lambda p: quantize_unet_params(p, mode="w8"), params
    )
    model_a8 = UNet3DConditionModel(config=cfg, dtype=jnp.bfloat16,
                                    act_quant_fn=fake_quant_act)
    fn_a8 = make_unet_fn(model_a8)

    def unet_unit(p, x, t, text):
        eps, _ = fn(p, x, t, text, None)
        return eps

    def unet_unit_a8(p, x, t, text):
        eps, _ = fn_a8(p, x, t, text, None)
        return eps

    t_unit = jax.ShapeDtypeStruct((), jnp.int32)
    programs = {
        "invert_captured": (invert_captured, (params, x0, cond_src)),
        "edit_cached": (edit_cached, (params, xt_sds, cond, uncond, cached_sds)),
        "e2e_cached": (e2e_cached, (params, x0, cond_src, cond, uncond)),
        "null_text_unit_fwd": (
            jax.jit(unit_fwd), (params, lat_f32, t_sds, u_sds)
        ),
        "null_text_unit_inner": (
            jax.jit(unit_inner),
            (params, u_sds, lat_f32, t_sds, lat_f32, lat_f32),
        ),
        "unet_unit_fp": (jax.jit(unet_unit), (params, xt_unit, t_unit, cond)),
        "unet_unit_w8": (
            jax.jit(unet_unit), (params_w8, xt_unit, t_unit, cond)
        ),
        "unet_unit_w8a8": (
            jax.jit(unet_unit_a8), (params_w8, xt_unit, t_unit, cond)
        ),
    }

    # straight-line DeepCache blocks: one full forward CAPTURING the deep
    # feature (the final up block's input) + K−1 SHALLOW forwards reusing
    # it — exactly what reuse_schedule="uniform:K" runs per K-step window
    # inside the fused edit scan, unrolled here so the static flop count
    # is the true K-step count
    # each step gets its OWN abstract latent and timestep (as the real
    # scan does): with a shared x the shallow forward is an exact
    # subcomputation of the capture forward and XLA CSE deletes it,
    # zeroing the count the unit exists to measure
    def make_reuse_unit(k):
        def reuse_unit(p, xs, ts, text):
            (eps, deep), _ = fn(p, xs[0], ts[0], text, None,
                                deep_mode="capture")
            acc = eps
            for i in range(1, k):
                eps_s, _ = fn(p, xs[i], ts[i], text, None,
                              deep_mode="shallow", deep_feature=deep)
                acc = acc + eps_s
            return acc
        return jax.jit(reuse_unit)

    for k in sorted(set(int(k) for k in reuse_ks)):
        if k < 1:
            raise ValueError(f"reuse_unit K must be >= 1, got {k}")
        xs_unit = jax.ShapeDtypeStruct((k,) + xt_unit.shape, jnp.bfloat16)
        ts_unit = jax.ShapeDtypeStruct((k,), jnp.int32)
        programs[f"reuse_unit_{k}"] = (
            make_reuse_unit(k), (params, xs_unit, ts_unit, cond)
        )

    # few-step STUDENT units (ISSUE 16, bench.per_call_cost_records): the
    # student is the same UNet plus the distilled time-conditioning head
    # on ε, so one student step = unet_unit_fp + apply_time_head — the
    # fp-vs-distill flop delta is the head-overhead claim, and the N-step
    # unit (loop-free, per-step abstract inputs like the reuse units:
    # shared inputs would let XLA CSE collapse identical forwards) is the
    # true program a student:N frontier row runs
    from videop2p_tpu.train.distill import apply_time_head, init_time_head

    head = jax.eval_shape(lambda k: init_time_head(k, cfg),
                          jax.random.key(0))

    def distill_unit_fp(p, h, x, t, text):
        eps, _ = fn(p, x, t, text, None)
        return apply_time_head(h, eps, t)

    programs["distill_unit_fp"] = (
        jax.jit(distill_unit_fp), (params, head, xt_unit, t_unit, cond)
    )

    def make_distill_unit(n):
        def distill_unit(p, h, xs, ts, text):
            acc = None
            for i in range(n):
                eps, _ = fn(p, xs[i], ts[i], text, None)
                eps = apply_time_head(h, eps, ts[i])
                acc = eps if acc is None else acc + eps
            return acc
        return jax.jit(distill_unit)

    for n in sorted(set(int(n) for n in distill_ns)):
        if n < 1:
            raise ValueError(f"distill_unit N must be >= 1, got {n}")
        xs_unit = jax.ShapeDtypeStruct((n,) + xt_unit.shape, jnp.bfloat16)
        ts_unit = jax.ShapeDtypeStruct((n,), jnp.int32)
        programs[f"distill_unit_{n}"] = (
            make_distill_unit(n), (params, head, xs_unit, ts_unit, cond)
        )
    return programs


def unit_program_records(wanted: List[str], shards: int):
    """Build + analyze the requested ring/tp unit programs (names
    ``ring_unit_<variant>_f<F>`` / ``tp_unit_<gspmd|scatter>``) on a
    ``shards``-wide virtual mesh. Returns ``{name: record}`` with the
    comm accounting merged in; unknown unit names raise ValueError."""
    from videop2p_tpu.parallel import make_mesh

    import __graft_entry__ as graft

    ring_mesh = tp_mesh = None
    ring_cache: dict = {}
    tp_cache: dict = {}
    out = {}
    for name in wanted:
        if name.startswith("ring_unit_"):
            rest = name[len("ring_unit_"):]
            variant, _, fpart = rest.rpartition("_f")
            if not variant or not fpart.isdigit():
                raise ValueError(f"bad ring unit name {name!r} "
                                 "(want ring_unit_<variant>_f<frames>)")
            frames = int(fpart)
            if frames % shards:
                raise ValueError(f"{name!r}: {shards} shards cannot divide "
                                 f"{frames} frames")
            if ring_mesh is None:
                ring_mesh = make_mesh((1, shards, 1),
                                      devices=jax.devices()[:shards])
            if frames not in ring_cache:
                ring_cache[frames] = graft._ring_unit_records(ring_mesh, frames)
            if variant not in ring_cache[frames]:
                raise ValueError(f"unknown ring variant in {name!r}")
            out[name] = dict(ring_cache[frames][variant], shards=shards)
        elif name.startswith("tp_unit_"):
            variant = name[len("tp_unit_"):]
            if tp_mesh is None:
                tp_mesh = make_mesh((1, 1, shards),
                                    devices=jax.devices()[:shards])
            if not tp_cache:
                tp_cache = graft._tp_unit_records(tp_mesh)
            if variant not in tp_cache:
                raise ValueError(f"unknown tp unit {name!r} "
                                 f"(have {sorted(tp_cache)})")
            out[name] = dict(tp_cache[variant], shards=shards)
    return out


def main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(prog="cpu_cost_capture.py",
                                     description=__doc__)
    parser.add_argument("--frames", type=int, default=8)
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--tiny", action="store_true",
                        help="tiny UNet config (fast; used by tests)")
    parser.add_argument("--programs", type=str,
                        default="invert_captured,edit_cached,e2e_cached")
    parser.add_argument("--shards", type=int, default=8,
                        help="virtual device count for the ring/tp unit "
                             "programs")
    parser.add_argument("--ledger", type=str, default=None,
                        help="also append program_analysis events to this "
                             "run-ledger JSONL")
    args = parser.parse_args(argv[1:])

    from videop2p_tpu.obs.introspect import analyze_jitted

    wanted = [p.strip() for p in args.programs.split(",") if p.strip()]
    unit_wanted = [p for p in wanted
                   if p.startswith(("ring_unit_", "tp_unit_"))]
    if unit_wanted:
        # the unit programs shard over a virtual CPU mesh; the flag only
        # takes effect because no backend has initialized yet (this tool
        # always runs as a fresh subprocess)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.shards}"
            ).strip()

    pipeline_wanted = [p for p in wanted if p not in unit_wanted]
    reuse_ks = []
    distill_ns = []
    for p in pipeline_wanted:
        if p.startswith("reuse_unit_"):
            kpart = p[len("reuse_unit_"):]
            if not kpart.isdigit() or int(kpart) < 1:
                print(f"cpu_cost_capture: bad reuse unit name {p!r} "
                      "(want reuse_unit_<K>, K >= 1)", file=sys.stderr)
                return 2
            reuse_ks.append(int(kpart))
        elif p.startswith("distill_unit_") and p != "distill_unit_fp":
            npart = p[len("distill_unit_"):]
            if not npart.isdigit() or int(npart) < 1:
                print(f"cpu_cost_capture: bad distill unit name {p!r} "
                      "(want distill_unit_fp or distill_unit_<N>, N >= 1)",
                      file=sys.stderr)
                return 2
            distill_ns.append(int(npart))
    programs = build_abstract_programs(args.frames, args.steps, args.tiny,
                                       reuse_ks=reuse_ks,
                                       distill_ns=distill_ns)
    unknown = [p for p in pipeline_wanted if p not in programs]
    if unknown:
        print(f"cpu_cost_capture: unknown programs {unknown} "
              f"(have {sorted(programs)} + reuse_unit_<K> + "
              f"distill_unit_<N> + "
              f"ring_unit_<variant>_f<F> + tp_unit_<gspmd|scatter>)",
              file=sys.stderr)
        return 2
    try:
        unit_records = unit_program_records(unit_wanted, args.shards)
    except ValueError as e:
        print(f"cpu_cost_capture: {e}", file=sys.stderr)
        return 2

    ledger = None
    if args.ledger:
        from videop2p_tpu.obs.ledger import RunLedger

        ledger = RunLedger(args.ledger, meta={"tool": "cpu_cost_capture",
                                              "frames": args.frames,
                                              "steps": args.steps}).activate()
    rc = 0
    for name in wanted:
        if name in unit_records:
            rec = unit_records[name]
        else:
            jitted, abstract_args = programs[name]
            rec = analyze_jitted(jitted, *abstract_args)
        if rec is None:
            print(f"cpu_cost_capture: analysis failed for {name}",
                  file=sys.stderr)
            rc = 1
            continue
        rec = {"program": name, "backend": "cpu", "frames": args.frames,
               "steps": args.steps, **rec}
        print(json.dumps(rec), flush=True)  # line per program: timeout-safe
        if ledger is not None:
            ledger.program_analysis(name, {k: v for k, v in rec.items()
                                           if k != "program"})
    if ledger is not None:
        ledger.close()
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
