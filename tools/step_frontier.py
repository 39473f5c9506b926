"""Execute the cached fast path's latency-vs-quality step frontier on CPU.

Usage:  python tools/step_frontier.py [--tiny] [--frames 2]
            [--base_steps 50] [--steps 50,20,8]
            [--variants w8+off,off+uniform:2,w8+uniform:2]

Runs ONE ``--base_steps`` captured DDIM inversion and then the cached
controlled edit at each requested step count via exact timestep-subset
schedules (``bench.run_step_frontier`` — the same function the healthy
bench runs on the accelerator), scoring every variant against the
full-step edit with the obs/quality metrics (PSNR / SSIM /
background-preservation / adjacent-frame consistency) and asserting the
source replay stays exact (``src_err == 0.0``) at every step count.

This is bench.py's backend-down fallback for the ISSUE-8 frontier
acceptance: quality-vs-steps is backend-independent math, so the 8- and
20-step variants can be proven to run e2e from a 50-step inversion EVERY
round — wall-clock is recorded but disclosed as CPU(-tiny), never a TPU
claim. One JSON line per step count, flushed as each finishes, so a
caller's timeout keeps whatever completed. ``--tiny`` swaps in the tiny
UNet (the test/backend-down configuration; SD scale would take hours of
CPU execute).

``--variants`` (ISSUE 15) adds per-call-cost rows to the same frontier:
a comma list of ``<quant_mode>+<reuse_schedule>`` pairs (each split on
its first ``+``; ``custom:`` schedules are comma-bearing and so not
expressible here — use ``off``/``uniform:K``), each running the
full-step cached edit with int8-quantized weights and/or a DeepCache
reuse schedule and scored against the full-precision full-step edit.
The replay-exactness invariant applies to these rows too: ``src_err``
must stay 0.0 under both knobs.

Student rows (ISSUE 16): a variant may instead be
``student:<N>+<quant_mode>+<reuse_schedule>`` (e.g.
``student:2+w8+uniform:2``) — the consistency-distilled few-step
student at ``N`` steps of the base schedule's exact timestep subset,
composed with the same quant/reuse knobs. The tool runs these with the
identity-initialized time-conditioning head (the untrained-student
baseline, value-exact with the teacher), so the rows prove the composed
program runs e2e and its ``src_err`` stays 0.0; quality claims for a
TRAINED student come from the distillation pipeline's ledger through
``tools/obs_diff.py``. Duplicate ``--variants`` entries are rejected
(exit 2) rather than silently recorded as duplicate frontier rows.
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
# process beats JAX_PLATFORMS (as in tools/cpu_cost_capture.py)
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from videop2p_tpu.cli.common import enable_compile_cache  # noqa: E402

enable_compile_cache()


def main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(prog="step_frontier.py",
                                     description=__doc__)
    parser.add_argument("--frames", type=int, default=2)
    parser.add_argument("--base_steps", type=int, default=50)
    parser.add_argument("--steps", type=str, default="50,20,8")
    parser.add_argument("--tiny", action="store_true",
                        help="tiny UNet config (the CPU-executable scale)")
    parser.add_argument("--no_time", action="store_true",
                        help="skip the timing dispatches (quality only)")
    parser.add_argument("--variants", type=str, default="",
                        help="comma list of quant_mode+reuse_schedule pairs "
                             "(e.g. w8+off,off+uniform:2,w8+uniform:2) "
                             "and/or student:N+quant_mode+reuse_schedule "
                             "rows (e.g. student:2+w8+uniform:2)")
    args = parser.parse_args(argv[1:])

    variants = []
    seen = set()
    for entry in args.variants.split(","):
        entry = entry.strip()
        if not entry:
            continue
        # the student prefix is checked BEFORE the first-"+" split: a
        # naive split would hand "student:2" to quant-mode validation
        # and produce a confusing downstream error
        if entry.startswith("student:"):
            parts = entry[len("student:"):].split("+", 2)
            if len(parts) != 3 or not parts[0].isdigit() or int(parts[0]) < 1:
                print(f"step_frontier: --variants entry {entry!r} is not "
                      "student:<N>+<quant_mode>+<reuse_schedule> (N >= 1)",
                      file=sys.stderr)
                return 2
            variant = (int(parts[0]), parts[1], parts[2])
        elif "+" in entry:
            qm, rs = entry.split("+", 1)
            variant = (qm, rs)
        else:
            print(f"step_frontier: --variants entry {entry!r} is not "
                  "<quant_mode>+<reuse_schedule> or "
                  "student:<N>+<quant_mode>+<reuse_schedule>",
                  file=sys.stderr)
            return 2
        if variant in seen:
            print(f"step_frontier: duplicate --variants entry {entry!r} — "
                  "each variant yields one frontier row; a repeat would be "
                  "silently recorded as a duplicate row", file=sys.stderr)
            return 2
        seen.add(variant)
        variants.append(variant)

    import bench

    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
    from videop2p_tpu.core import DDIMScheduler
    from videop2p_tpu.pipelines import make_unet_fn

    if args.tiny:
        cfg = UNet3DConfig.tiny()
        lat, ctx_dim = cfg.sample_size, cfg.cross_attention_dim
        dtype = jnp.float32
    else:
        cfg = UNet3DConfig.sd15(frame_attention="chunked", group_norm="xla")
        lat, ctx_dim, dtype = 64, 768, jnp.bfloat16
    model = UNet3DConditionModel(config=cfg, dtype=dtype)
    fn = make_unet_fn(model)
    sched = DDIMScheduler.create_sd()
    key = jax.random.key(0)
    x0 = jax.random.normal(key, (1, args.frames, lat, lat, 4), dtype)
    cond = jax.random.normal(jax.random.fold_in(key, 1),
                             (2, 77, ctx_dim), dtype)
    uncond = jnp.zeros((77, ctx_dim), dtype)
    params = jax.jit(model.init)(
        jax.random.fold_in(key, 2), x0[:, :2], jnp.asarray(10), cond[:1]
    )

    student_head = None
    if any(len(v) == 3 for v in variants):
        # the untrained-student baseline: identity-initialized time head
        # (zero-init output layer ⇒ value-exact with the teacher forward)
        from videop2p_tpu.train.distill import init_time_head

        student_head = init_time_head(jax.random.key(0), cfg)

    step_counts = [int(s) for s in args.steps.split(",") if s.strip()]
    records, _ = bench.run_step_frontier(
        fn, params, sched, cond, uncond, x0,
        base_steps=args.base_steps, step_counts=step_counts,
        timed=not args.no_time, variants=tuple(variants),
        student_head=student_head,
    )
    rc = 0
    for rec in records:
        rec = {"backend": "cpu", "tiny": bool(args.tiny), **rec}
        if rec["src_err"] != 0.0:
            rc = 1  # the replay-exactness invariant broke — say so loudly
        print(json.dumps(rec), flush=True)  # line per step: timeout-safe
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
