"""ProgramSet: build, compile and instrument the edit programs ONCE.

The one-shot CLIs each carried their own near-identical wiring — model
assembly, scheduler construction, ``instrumented_jit`` wrappers, capture
budgeting — rebuilt (and recompiled) per invocation. A :class:`ProgramSet`
extracts that wiring behind one object keyed by a :class:`ProgramSpec`
(checkpoint identity, geometry, step count): build it once, and every
subsequent request reuses the warm compiled programs.

What makes the programs *warm across requests* rather than per-request:
:class:`~videop2p_tpu.control.controllers.ControlContext` and
:class:`~videop2p_tpu.pipelines.cached.CachedSource` are flax PyTreeNodes,
so they are passed as TRACED jit arguments here (the CLIs close over them,
which bakes their arrays in as constants). Two requests with the same
controller *structure* (kind, windows, blend-or-not) but different prompts,
equalizers or clips therefore hit the same compiled executable — the jit
cache key is the treedef + leaf shapes, exactly the batching compatibility
key (:func:`videop2p_tpu.serve.batching.compat_key`).

Every program goes through :func:`~videop2p_tpu.obs.ledger.instrumented_jit`,
so with an active :class:`~videop2p_tpu.obs.RunLedger` the serving engine
gets compile attribution, per-program XLA analyses, and the ``--latency``
reservoirs for free — the same machinery the CLIs use.

Stdlib+numpy+jax only (model/pipeline code reached through the package) —
the import-guard test walks this package like ``obs/``.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ProgramSpec", "ProgramSet", "ProgramCache", "MASK_TH"]

# the Stage-2 working-point constant (cli/run_videop2p.py uses the same)
MASK_TH = (0.3, 0.3)

# bounded per-set program cache: (name, statics) -> instrumented callable
_PROGRAMS_MAX = 32


@dataclass(frozen=True)
class ProgramSpec:
    """Everything that determines a program set's compiled identity.

    Two requests agreeing on a spec (plus controller structure) share every
    compiled program; the engine and the program cache key on
    :meth:`fingerprint`, which uses checkpoint CONTENT identity — re-tuning
    a checkpoint in place produces a different fingerprint, never a stale
    warm program over new weights.
    """

    checkpoint: Optional[str] = None
    width: int = 512
    video_len: int = 8
    steps: int = 50
    guidance_scale: float = 7.5
    tiny: bool = False
    mixed_precision: str = "fp32"
    seed: int = 0
    # device mesh "dp,sp,tp": sp/tp shard the model (cli.common.setup_mesh);
    # dp > 1 is the serving data axis — batched dispatches shard their
    # leading request axis across it (vmap dispatch mode)
    mesh: Optional[str] = None
    # sharded-program schedule knobs (parallel/ring.py, parallel/mesh.py):
    # the ring rotation variant on sequence-parallel meshes and the
    # Megatron reduce-scatter seam on tensor-parallel ones. Both enter the
    # fingerprint — a ring/tp schedule change builds DIFFERENT compiled
    # programs, and a warm set keyed without them would silently serve the
    # old schedule (or collide two specs onto one store namespace)
    ring_variant: str = "overlap"
    tp_collectives: str = "gspmd"
    # serving is the cached fast path: no null-text backward, so no remat
    gradient_checkpointing: bool = False
    # per-UNet-call cost levers (ISSUE 15). quant_mode quantizes the UNet
    # weights at SET BUILD time (models/convert.quantize_unet_params) — it
    # cannot vary per request, only per program set; reuse_schedule is the
    # spec's DEFAULT cross-step deep-feature reuse (pipelines/reuse.py) and
    # per-request values are admitted against the warmed-schedule list.
    # Both enter the fingerprint: a quantized set serves different weights
    # and a reuse set different scan bodies — sharing a store namespace
    # with the full-precision set would silently mix outputs
    quant_mode: str = "off"
    reuse_schedule: str = "off"
    # consistency-distilled few-step student (train/distill.py): path to a
    # distilled checkpoint (trainable subset + time-conditioning head). In
    # the fingerprint by CONTENT identity so warm caches and the inversion
    # store never collide across student/teacher — the inversion itself is
    # always the TEACHER's (the student rides the same captured replay)
    student_ckpt: Optional[str] = None

    def resolved(self) -> "ProgramSpec":
        """The tiny-width rule the CLI applies: the tiny VAE downsamples
        2×, not 8× — keep latents at the tiny UNet's 8×8 working point."""
        if self.tiny and self.width == 512:
            return replace(self, width=16)
        return self

    def fingerprint(self) -> str:
        from videop2p_tpu.utils.inv_cache import (
            content_fingerprint,
            inversion_cache_key,
        )

        spec = self.resolved()
        return inversion_cache_key(
            kind="program_spec",
            checkpoint=(content_fingerprint(spec.checkpoint)
                        if spec.checkpoint else "<random-init>"),
            student_ckpt=(content_fingerprint(spec.student_ckpt)
                          if spec.student_ckpt else "<none>"),
            **{k: getattr(spec, k) for k in (
                "width", "video_len", "steps", "guidance_scale", "tiny",
                "mixed_precision", "seed", "mesh", "ring_variant",
                "tp_collectives", "gradient_checkpointing",
                "quant_mode", "reuse_schedule",
            )},
        )


def _parse_mesh(mesh: Optional[str]) -> Tuple[int, int, int]:
    if not mesh:
        return (1, 1, 1)
    shape = tuple(int(t) for t in str(mesh).split(","))
    if len(shape) != 3:
        raise ValueError(f"mesh must be dp,sp,tp — got {mesh!r}")
    return shape


class ProgramSet:
    """Warm, instrumented device programs for one :class:`ProgramSpec`.

    Built once per (checkpoint, geometry, steps) key; the serving engine,
    the CLIs and the UI all dispatch through the same instances, so the
    program users run IS the program the server batches and the obs stack
    measures.
    """

    def __init__(self, spec: ProgramSpec, *, bundle: Any = None):
        from videop2p_tpu.cli.common import build_models, setup_mesh
        from videop2p_tpu.models.quant import fake_quant_act, validate_quant_mode
        from videop2p_tpu.pipelines import make_unet_fn
        from videop2p_tpu.pipelines.reuse import validate_reuse_schedule

        self.spec = spec = spec.resolved()
        quant_mode = validate_quant_mode(spec.quant_mode)
        validate_reuse_schedule(spec.reuse_schedule, spec.steps)
        self.dtype = {"fp16": jnp.bfloat16, "bf16": jnp.bfloat16,
                      "fp32": jnp.float32, "no": jnp.float32}[spec.mixed_precision]
        dp, sp, tp = _parse_mesh(spec.mesh)
        if quant_mode != "off" and (sp > 1 or tp > 1):
            raise ValueError(
                f"quant_mode={quant_mode!r} is not supported on a "
                "model-parallel mesh — setup_mesh walks the param tree to "
                "assign shardings and QuantizedTensor leaves would need "
                "per-leaf (qvalue, scale) sharding rules; serve quantized "
                "sets on dp-only meshes"
            )
        if bundle is None:
            bundle = build_models(
                spec.checkpoint,
                dtype=self.dtype,
                frame_attention="chunked" if (sp > 1 or tp > 1) else "auto",
                tiny=spec.tiny,
                seed=spec.seed,
                gradient_checkpointing=spec.gradient_checkpointing,
            )
        self.bundle = bundle
        self.student_params = None
        self.student_head = None
        if spec.student_ckpt:
            if sp > 1 or tp > 1:
                raise ValueError(
                    "student_ckpt is not supported on a model-parallel mesh "
                    "— setup_mesh shards bundle.unet_params only; the "
                    "student's param tree would stay unsharded and every "
                    "student dispatch would mix shardings. Serve student "
                    "sets on dp-only meshes"
                )
            # restore against the FULL-PRECISION teacher tree — the student
            # is the teacher's frozen majority + the distilled trainable
            # subset + the time-conditioning head; quantization (below)
            # then applies to both param trees identically
            from videop2p_tpu.train.distill import load_student

            merged, self.student_head = load_student(
                spec.student_ckpt, bundle.unet_params["params"],
                bundle.unet.config,
            )
            self.student_params = dict(bundle.unet_params, params=merged)
        if quant_mode != "off":
            from videop2p_tpu.models.convert import quantize_unet_params

            if quant_mode == "w8a8":
                # the a8 half: dynamic per-tensor fake-quant at the
                # attention Dense boundaries, threaded like row_parallel_dot
                bundle.unet = bundle.unet.clone(act_quant_fn=fake_quant_act)
            # the w8 half: 1-byte weights become the program inputs;
            # make_unet_fn dequantizes inside the trace
            bundle.unet_params = quantize_unet_params(
                bundle.unet_params, mode=quant_mode
            )
            if self.student_params is not None:
                # the student serves the SAME quantized format as the
                # teacher — student rows on the frontier compose with w8
                # rather than silently reverting to fp weights
                self.student_params = quantize_unet_params(
                    self.student_params, mode=quant_mode
                )
        self.mesh = None
        self.data_axis_size = dp
        if sp > 1 or tp > 1:
            # model-internal sharding: the CLIs' setup_mesh wires ring
            # attention / sharded GroupNorm and shards the params (dp must
            # be 1 on this path — single-clip model parallelism)
            self.mesh = setup_mesh(
                bundle, spec.mesh, spec.video_len,
                ring_variant=spec.ring_variant,
                tp_collectives=spec.tp_collectives,
            )
        elif dp > 1:
            # pure serving data parallelism: params replicate, batched
            # dispatches shard their leading request axis over "data".
            # Unlike the model-parallel path the mesh takes the FIRST dp
            # devices rather than requiring dp == device_count — a serving
            # process may dedicate a subset of a host's chips to one spec.
            from videop2p_tpu.parallel import make_mesh

            self.mesh = make_mesh((dp, sp, tp), devices=jax.devices()[:dp])
            replicated = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()
            )
            self.bundle.unet_params = jax.device_put(
                self.bundle.unet_params, replicated
            )
            if self.student_params is not None:
                self.student_params = jax.device_put(
                    self.student_params, replicated
                )
                self.student_head = jax.device_put(
                    self.student_head, replicated
                )
        self.unet_fn = make_unet_fn(bundle.unet)
        self.scheduler = bundle.make_scheduler()
        self._programs: Dict[Tuple, Callable] = {}
        self.warmed: Optional[Dict[str, Any]] = None

    # ---- program cache ---------------------------------------------------

    def _program(self, key: Tuple, build: Callable[[], Callable]) -> Callable:
        prog = self._programs.get(key)
        if prog is None:
            while len(self._programs) >= _PROGRAMS_MAX:
                self._programs.pop(next(iter(self._programs)))
            prog = self._programs[key] = build()
        return prog

    # ---- host-side helpers ----------------------------------------------

    def encode_prompts(self, prompts: Sequence[str]) -> jax.Array:
        from videop2p_tpu.cli.common import encode_prompts

        return encode_prompts(self.bundle, list(prompts))

    def controller(
        self,
        prompts: Sequence[str],
        *,
        is_word_swap: bool = False,
        cross_replace_steps: float = 0.2,
        self_replace_steps: float = 0.5,
        blend_word: Optional[Sequence[str]] = None,
        eq_params: Optional[Dict] = None,
        mask_th: Tuple[float, float] = MASK_TH,
        steps: Optional[int] = None,
    ):
        """The CLI's controller construction, spec-bound (num_steps);
        ``steps`` overrides for a timestep-subset (few-step) edit, whose
        gates live in subset-step space."""
        from videop2p_tpu.control import make_controller

        blend_words = None
        if blend_word:
            blend_words = ((blend_word[0],), (blend_word[1],))
        return make_controller(
            list(prompts),
            self.bundle.tokenizer,
            num_steps=int(steps) if steps else self.spec.steps,
            is_replace_controller=bool(is_word_swap),
            cross_replace_steps=cross_replace_steps,
            self_replace_steps=self_replace_steps,
            blend_words=blend_words,
            equalizer_params=dict(eq_params) if eq_params else None,
            mask_th=mask_th,
        )

    def frames_to_video(self, frames: np.ndarray) -> jax.Array:
        """(F, H, W, 3) uint8 frames → the (1, F, H, W, 3) [-1, 1] float
        tensor the encode program takes."""
        return jnp.asarray(np.asarray(frames), jnp.float32)[None] / 127.5 - 1.0

    # ---- programs --------------------------------------------------------

    def encode(self, video: jax.Array, key: jax.Array) -> jax.Array:
        """VAE-encode at the posterior mean (inversion fidelity) — the
        ``vae_encode`` program both CLIs dispatch."""
        from videop2p_tpu.models import encode_video
        from videop2p_tpu.obs import instrumented_jit

        prog = self._program(("vae_encode",), lambda: instrumented_jit(
            lambda vp, vid, k: encode_video(
                self.bundle.vae, vp, vid.astype(self.dtype), k, sample=False
            ).astype(jnp.float32),
            program="vae_encode",
        ))
        return prog(self.bundle.vae_params, video, key)

    def decode(self, latents: jax.Array) -> jax.Array:
        """Latents → [0, 1] video — the ``vae_decode`` program."""
        from videop2p_tpu.models import decode_video
        from videop2p_tpu.obs import instrumented_jit

        prog = self._program(("vae_decode",), lambda: instrumented_jit(
            lambda vp, x: (decode_video(
                self.bundle.vae, vp, x.astype(self.dtype), sequential=True
            ).astype(jnp.float32) + 1.0) / 2.0,
            program="vae_decode",
        ))
        return prog(self.bundle.vae_params, latents)

    def sample(self, x_t: jax.Array, cond: jax.Array, uncond: jax.Array,
               key: jax.Array, *, steps: Optional[int] = None,
               guidance_scale: Optional[float] = None) -> jax.Array:
        """Uncontrolled CFG sampling + decode as one program (the UI's
        inference tab) — label ``sample_decode``."""
        from videop2p_tpu.models import decode_video
        from videop2p_tpu.obs import instrumented_jit
        from videop2p_tpu.pipelines import edit_sample

        steps = int(steps or self.spec.steps)
        guidance = float(self.spec.guidance_scale
                         if guidance_scale is None else guidance_scale)

        def build():
            def fn(params, vp, x, cond, uncond, k):
                out = edit_sample(
                    self.unet_fn, params, self.scheduler, x, cond, uncond,
                    num_inference_steps=steps, guidance_scale=guidance, key=k,
                )
                vids = decode_video(
                    self.bundle.vae, vp, out.astype(self.dtype), sequential=True
                )
                return (vids.astype(jnp.float32) + 1.0) / 2.0

            return instrumented_jit(fn, program="sample_decode")

        prog = self._program(("sample_decode", steps, guidance), build)
        return prog(self.bundle.unet_params, self.bundle.vae_params,
                    x_t, cond, uncond, key)

    def capture_plan(self, ctx, latents: jax.Array, cond_src: jax.Array):
        """The CLI's cached-mode capture decision for this spec: gate
        windows from the controller plus the escalating per-chip maps
        budget (bf16 → float8 temporal storage). Returns
        ``(cross_len, self_window, tm_dtype)``; raises when even float8
        maps exceed the budget — the serving engine has no live-source
        fallback path."""
        from videop2p_tpu.pipelines.cached import capture_windows
        from videop2p_tpu.pipelines.fast import capture_shapes, choose_cached_maps

        cross_len, self_window = capture_windows(ctx, self.spec.steps)
        budget_gb = float(os.environ.get("VIDEOP2P_CACHED_MAPS_BUDGET_GB", "6"))

        def shapes_for(tm_dtype):
            return capture_shapes(
                self.unet_fn, self.bundle.unet_params, self.scheduler,
                latents, cond_src, ctx,
                num_inference_steps=self.spec.steps,
                cross_len=cross_len, self_window=self_window,
                temporal_maps_dtype=tm_dtype,
            )[1]

        _, sp, _ = _parse_mesh(self.spec.mesh)
        fits, tm_dtype, map_gb, per_chip_gb = choose_cached_maps(
            shapes_for, sp=sp, budget_gb=budget_gb
        )
        if not fits:
            raise RuntimeError(
                f"cached-source capture needs {per_chip_gb:.1f} GiB/chip even "
                f"with float8 temporal maps (budget {budget_gb:.1f} GiB) — "
                "shrink the geometry or raise VIDEOP2P_CACHED_MAPS_BUDGET_GB"
            )
        return cross_len, self_window, tm_dtype

    def invert_capture(self, latents: jax.Array, cond_src: jax.Array, ctx,
                       key: jax.Array):
        """Capture-inversion of the source clip: ``(trajectory, CachedSource)``
        — the store-able products. One program per (windows, blend,
        storage-dtype) static tuple; the controller's arrays never enter
        this program, so every clip with the same capture plan reuses it."""
        from videop2p_tpu.obs import instrumented_jit
        from videop2p_tpu.pipelines import ddim_inversion_captured

        cross_len, self_window, tm_dtype = self.capture_plan(ctx, latents, cond_src)
        capture_blend = ctx is not None and ctx.blend is not None
        statics = ("serve_invert", cross_len, self_window, capture_blend,
                   None if tm_dtype is None else jnp.dtype(tm_dtype).name)

        def build():
            def fn(params, x, cond, k):
                return ddim_inversion_captured(
                    self.unet_fn, params, self.scheduler, x, cond,
                    num_inference_steps=self.spec.steps,
                    cross_len=cross_len, self_window=self_window,
                    capture_blend=capture_blend,
                    key=k, temporal_maps_dtype=tm_dtype,
                )

            return instrumented_jit(fn, program="serve_invert")

        prog = self._program(statics, build)
        return prog(self.bundle.unet_params, latents, cond_src, key)

    def step_plan(self, steps: Optional[int] = None):
        """Resolve a per-request step count against the spec's base steps:
        ``(steps, positions)`` where ``positions`` is None at the base count
        and the exact timestep-subset positions otherwise (the cached fast
        path then runs few-step from the SAME base-steps inversion)."""
        steps = int(steps) if steps else self.spec.steps
        if steps == self.spec.steps:
            return steps, None
        if not 1 <= steps <= self.spec.steps:
            raise ValueError(
                f"steps={steps} outside [1, {self.spec.steps}] (the spec's "
                "base step count — inversions are captured at the base grid)"
            )
        return steps, tuple(
            int(p) for p in self.scheduler.subset_positions(
                self.spec.steps, steps
            )
        )

    def _edit_fn(self, steps: Optional[int] = None,
                 positions: Optional[Tuple[int, ...]] = None,
                 reuse: Optional[str] = None,
                 student: bool = False):
        """The per-request edit+decode subcomputation — shared verbatim by
        the singleton program and every batched variant, which is what
        makes scan-mode batching bit-exact vs singleton dispatch.
        ``steps``/``positions``: the timestep-subset fast path (few-step
        serving from the base-steps inversion products). ``reuse``: a
        cross-step deep-feature reuse schedule (pipelines/reuse.py) — a
        STATIC knob baked into the compiled scan body. ``student``: run
        the edit scan as the consistency-distilled student — the head
        arrays bake in as program constants (a few KiB; one student per
        spec) while the caller passes the student param tree; the source
        stream is still the exact capture replay, so ``src_err`` keeps
        its 0.0 contract."""
        from videop2p_tpu.models import decode_video
        from videop2p_tpu.pipelines import edit_sample

        guidance = self.spec.guidance_scale
        steps = int(steps) if steps else self.spec.steps
        head = self.student_head if student else None
        if student and head is None:
            raise ValueError(
                "student edit requested but the spec has no student_ckpt — "
                "build the ProgramSet with ProgramSpec.student_ckpt set"
            )

        def fn(params, vp, cached, cond_all, uncond, ctx, anchor):
            out = edit_sample(
                self.unet_fn, params, self.scheduler,
                cached.src_latents[0], cond_all, uncond,
                num_inference_steps=steps, guidance_scale=guidance,
                ctx=ctx, source_uses_cfg=False, cached_source=cached,
                step_positions=positions, reuse_schedule=reuse,
                student_head=head,
            )
            vids = decode_video(
                self.bundle.vae, vp, out.astype(self.dtype), sequential=True
            )
            videos01 = (vids.astype(jnp.float32) + 1.0) / 2.0
            # stream 0 must be the exact inversion reconstruction: compare
            # against the ANCHOR (the encoded source latents stored with
            # the products) — 0.0 exactly when the store replay is intact
            src_err = jnp.max(jnp.abs(out[:1] - anchor)).astype(jnp.float32)
            return videos01, src_err

        return fn

    def _resolve_reuse(self, reuse: Optional[str], steps: int) -> str:
        """Per-call reuse schedule: None defers to the spec default;
        validated against THIS call's step count (a subset-steps edit has
        fewer positions for the schedule to land on)."""
        from videop2p_tpu.pipelines.reuse import validate_reuse_schedule

        if reuse is None:
            reuse = self.spec.reuse_schedule
        return validate_reuse_schedule(reuse, steps)

    def edit_decode(self, cached, cond_all, uncond, ctx, anchor, *,
                    steps: Optional[int] = None,
                    reuse: Optional[str] = None,
                    student: bool = False):
        """One request: cached-source controlled edit + VAE decode as one
        dispatch. Returns ``(videos01 (P,F,H,W,3), src_err scalar)``.
        ``steps`` < the spec's base count runs the timestep-subset fast
        path from the same inversion products (the controller must be
        built for that step count — :meth:`controller`'s ``steps=``).
        ``reuse``: cross-step deep-feature reuse schedule (None → the
        spec's default) — a distinct compiled program per schedule.
        ``student``: dispatch the consistency-distilled student program
        (distilled params + time-conditioning head) over the SAME teacher
        inversion products — a distinct compiled program per flag."""
        from videop2p_tpu.obs import instrumented_jit
        from videop2p_tpu.pipelines.reuse import reuse_label

        steps, positions = self.step_plan(steps)
        reuse = self._resolve_reuse(reuse, steps)
        if positions is not None and ctx is not None:
            # gate-coverage check BEFORE tracing: ctx enters the program as
            # a traced argument, where the in-pipeline check cannot run
            from videop2p_tpu.pipelines.cached import check_subset_windows

            check_subset_windows(ctx, cached, positions, steps)
        label = ("serve_edit" if steps == self.spec.steps
                 else f"serve_edit_s{steps}")
        rl = reuse_label(reuse)
        if rl:
            label += f"_r{rl}"
        if student:
            label += "_stu"
        inner = self._edit_fn(steps, positions, reuse, student)
        prog = self._program(
            ("serve_edit", steps, self.spec.guidance_scale, reuse, student),
            lambda: instrumented_jit(inner, program=label),
        )
        params = self.student_params if student else self.bundle.unet_params
        return prog(params, self.bundle.vae_params,
                    cached, cond_all, uncond, ctx, anchor)

    def edit_decode_batch(self, stacked_args, size: int, *,
                          dispatch: str = "scan",
                          steps: Optional[int] = None,
                          reuse: Optional[str] = None,
                          student: bool = False):
        """``size`` compatible requests stacked on a leading batch axis →
        one dispatch. ``stacked_args`` is the stacked
        ``(cached, cond_all, uncond, ctx, anchor)`` tree
        (:func:`videop2p_tpu.serve.batching.stack_items`).

        ``dispatch="scan"``: ``lax.map`` — per-item math identical to the
        singleton program (bit-exact, pinned by tests); ``"vmap"``:
        vectorized, and on a ``data``-mesh the batch axis is sharded
        across chips (true data-parallel serving, allclose-gated).
        ``steps``: the per-request step count (the batch planner only
        groups same-steps requests — compat keys carry it); subset-window
        validation happens per request at resolve time, before stacking."""
        from videop2p_tpu.obs import instrumented_jit

        if dispatch not in ("scan", "vmap"):
            raise ValueError(f"dispatch must be 'scan' or 'vmap', got {dispatch!r}")
        from videop2p_tpu.pipelines.reuse import reuse_label

        steps, positions = self.step_plan(steps)
        reuse = self._resolve_reuse(reuse, steps)
        inner = self._edit_fn(steps, positions, reuse, student)
        suffix = "" if steps == self.spec.steps else f"_s{steps}"
        rl = reuse_label(reuse)
        if rl:
            suffix += f"_r{rl}"
        if student:
            suffix += "_stu"

        def build():
            def fn(params, vp, stacked):
                one = lambda xs: inner(params, vp, *xs)  # noqa: E731
                if dispatch == "scan":
                    return jax.lax.map(one, stacked)
                return jax.vmap(one)(stacked)

            return instrumented_jit(
                fn, program=f"serve_edit_b{size}_{dispatch}{suffix}"
            )

        prog = self._program(
            ("serve_edit_batch", size, dispatch,
             steps, self.spec.guidance_scale, reuse, student),
            build,
        )
        stacked_args = self._shard_batch(stacked_args, size)
        params = self.student_params if student else self.bundle.unet_params
        return prog(params, self.bundle.vae_params, stacked_args)

    def _shard_batch(self, stacked_args, size: int):
        """On a serving data mesh, put the batch axis on the ``data`` mesh
        axis (leading-dim sharding) so a vmap dispatch partitions requests
        across chips; replicates when the batch does not divide it."""
        if self.mesh is None or self.data_axis_size <= 1:
            return stacked_args
        if size % self.data_axis_size:
            return stacked_args
        from videop2p_tpu.parallel.mesh import AXIS_DATA

        sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(AXIS_DATA)
        )
        return jax.device_put(stacked_args, sharding)

    # ---- warmup ----------------------------------------------------------

    def warm(
        self,
        prompts: Sequence[str] = ("a video", "an edited video"),
        *,
        controller_kwargs: Optional[Dict] = None,
        batch_sizes: Sequence[int] = (),
        dispatch: str = "scan",
        step_buckets: Sequence[int] = (),
        reuse_schedules: Sequence[str] = (),
        student_steps: Sequence[int] = (),
    ) -> Dict[str, Any]:
        """Compile (and execute once, on zeros) the request-path programs:
        encode → invert-capture → edit+decode, plus any batched variants
        and any few-step (``step_buckets``) variants — every bucket runs
        from the SAME base-steps inversion via exact timestep subsets.
        The warm structure should match expected traffic (same prompt
        count / controller structure); mismatched requests still work,
        they just pay their own first compile. Returns a summary the
        ``/healthz`` endpoint reports (``steps`` is the warmed-bucket list
        the engine admits per-request ``steps`` against; ``reuse`` the
        warmed reuse-schedule list — the spec default plus
        ``reuse_schedules`` — admitted the same way; ``quant`` the set's
        one-and-only quant mode, fixed at build; ``student`` the warmed
        few-step student buckets — requires ``student_ckpt`` on the spec,
        and per-request ``student=True`` is admitted against it)."""
        t0 = time.perf_counter()
        spec = self.spec
        ctx = self.controller(prompts, **dict(controller_kwargs or {}))
        key = jax.random.key(spec.seed)
        frames = np.zeros((spec.video_len, spec.width, spec.width, 3), np.uint8)
        latents = self.encode(self.frames_to_video(frames), key)
        traj, cached = self.invert_capture(
            latents, self.encode_prompts(prompts[:1]), ctx, key
        )[:2]
        cond_all = self.encode_prompts(prompts)
        uncond = self.encode_prompts([""])[0]
        anchor = latents
        videos, src_err = self.edit_decode(cached, cond_all, uncond, ctx, anchor)
        jax.block_until_ready(videos)
        for size in batch_sizes:
            if size <= 1:
                continue
            from videop2p_tpu.serve.batching import stack_items

            stacked = stack_items(
                [(cached, cond_all, uncond, ctx, anchor)] * size, size
            )
            jax.block_until_ready(
                self.edit_decode_batch(stacked, size, dispatch=dispatch)[0]
            )
        warmed_steps = {spec.steps}
        for s in step_buckets:
            s = int(s)
            if s == spec.steps:
                continue
            ctx_s = self.controller(
                prompts, steps=s, **dict(controller_kwargs or {})
            )
            jax.block_until_ready(self.edit_decode(
                cached, cond_all, uncond, ctx_s, anchor, steps=s
            )[0])
            warmed_steps.add(s)
        warmed_reuse = {self._resolve_reuse(None, spec.steps)}
        for r in reuse_schedules:
            r = self._resolve_reuse(str(r), spec.steps)
            if r in warmed_reuse:
                continue
            jax.block_until_ready(self.edit_decode(
                cached, cond_all, uncond, ctx, anchor, reuse=r
            )[0])
            warmed_reuse.add(r)
        warmed_student: set = set()
        if student_steps and self.student_head is None:
            raise ValueError(
                "student_steps given but the spec has no student_ckpt — "
                "nothing to warm the student buckets with"
            )
        for s in student_steps:
            s = int(s)
            if s in warmed_student:
                continue
            ctx_s = self.controller(
                prompts, steps=s, **dict(controller_kwargs or {})
            ) if s != spec.steps else ctx
            jax.block_until_ready(self.edit_decode(
                cached, cond_all, uncond, ctx_s, anchor,
                steps=s, student=True,
            )[0])
            warmed_student.add(s)
        self.warmed = {
            "seconds": round(time.perf_counter() - t0, 3),
            "prompts": list(prompts),
            "batch_sizes": sorted({1, *[int(s) for s in batch_sizes]}),
            "steps": sorted(warmed_steps),
            "reuse": sorted(warmed_reuse),
            "quant": spec.quant_mode,
            "student": sorted(warmed_student),
            "src_err": float(np.asarray(jax.device_get(src_err))),
        }
        return self.warmed


class ProgramCache:
    """Bounded spec-keyed cache of :class:`ProgramSet` instances — the
    multi-tenant layer (one warm set per checkpoint/geometry/steps key)."""

    def __init__(self, max_sets: int = 4):
        self.max_sets = int(max_sets)
        self._sets: "Dict[str, ProgramSet]" = {}

    def get(self, spec: ProgramSpec) -> ProgramSet:
        key = spec.fingerprint()
        ps = self._sets.get(key)
        if ps is None:
            while len(self._sets) >= self.max_sets:
                self._sets.pop(next(iter(self._sets)))
            ps = self._sets[key] = ProgramSet(spec)
        return ps

    def __len__(self) -> int:
        return len(self._sets)
