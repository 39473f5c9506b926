"""Stage-1 one-shot tuning entry point.

TPU-native re-design of /root/reference/run_tuning.py: same YAML schema
(configs/rabbit-jump-tune.yaml) and flag surface, driving the pure
``train_step`` in a host loop with checkpointing, resume, and the
inversion+sampling validation the reference runs every ``validation_steps``
(run_tuning.py:346-375). Ends by writing the diffusers-layout pipeline dir
Stage 2 consumes (run_tuning.py:387-393).

Run:  python -m videop2p_tpu.cli.run_tuning --config configs/rabbit-jump-tune.yaml
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from videop2p_tpu.cli.common import (
    add_dependent_args,
    add_obs_args,
    build_models,
    build_token_model,
    check_model_family,
    dependent_suffix,
    encode_prompts,
    load_config,
    make_run_ledger,
    setup_mesh,
    enable_compile_cache,
)
from videop2p_tpu.obs import instrumented_jit
from videop2p_tpu.obs.spans import entry_imported, span
from videop2p_tpu.core import DDIMScheduler, DDPMScheduler, DependentNoiseSampler
from videop2p_tpu.data import SingleVideoDataset, TokenDocument
from videop2p_tpu.models import decode_video, encode_video
from videop2p_tpu.models.pipeline_io import save_pipeline
from videop2p_tpu.ops.attention import training_frame_attention
from videop2p_tpu.pipelines import ddim_inversion, edit_sample, make_unet_fn
from videop2p_tpu.train import (
    TrainState,
    TuneConfig,
    latest_checkpoint,
    loss_steps,
    make_lr_schedule,
    make_optimizer,
    next_token_loss,
    restore_checkpoint,
    save_checkpoint,
    train_steps,
)
from videop2p_tpu.utils.metrics import MetricsLogger
from videop2p_tpu.utils.profiling import phase_timer
from videop2p_tpu.utils.video_io import save_videos_grid

entry_imported()  # the end of the span `process.import`

# preemption safety (ISSUE 9 satellite): SIGTERM/SIGINT set this event; the
# loop saves a final checkpoint at the next chunk boundary and exits cleanly.
# Auto-resume (`resume_from_checkpoint: latest`) then continues BIT-IDENTICALLY
# (per-step noise keys derive from the run key and the absolute step inside
# train_steps; tests/test_train.py pins interrupted+resumed == uninterrupted).
_PREEMPT_EVENT = threading.Event()


def _preempt_handler(signum, frame):
    _PREEMPT_EVENT.set()


def _install_preempt_handlers():
    """Install SIGTERM/SIGINT → checkpoint-then-exit; returns a restore
    callable. No-op off the main thread (the signal API restriction) —
    embedded callers keep their own handlers."""
    if threading.current_thread() is not threading.main_thread():
        return lambda: None
    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(sig, _preempt_handler)
        except (ValueError, OSError):  # exotic embeddings
            continue
    def _restore():
        for sig, h in prev.items():
            try:
                signal.signal(sig, h)
            except (ValueError, OSError):
                continue
    return _restore


class _Tuned(NamedTuple):
    """What one model family hands ``main``'s loop: the train state, the
    step program ``(state, key, n) -> (state, losses, ...)``, what of a
    state a checkpoint holds, the validation run (None: the family has
    none), the export of the tuned model (returns the ledger's ``artifacts``
    fields), and the device mesh (None on one chip)."""

    state: TrainState
    program: Callable
    saved: Callable
    validate: Optional[Callable]
    export: Callable
    mesh: Any = None


def _setup_unet(
    pretrained_model_path, train_data, tx, tune_cfg, ek, *, dtype,
    gradient_checkpointing, tiny, seed, mesh, sampler, prediction_type,
    telemetry,
) -> _Tuned:
    """The inflated video UNet on one clip: models, the clip's latents and
    the prompt's text states, the train state, ``train_steps``."""
    n_frames = int(train_data.get("n_sample_frames", 8))
    with span("tune.build_models"):
        bundle = build_models(
            pretrained_model_path, dtype=dtype,
            frame_attention=training_frame_attention(),
            gradient_checkpointing=gradient_checkpointing, tiny=tiny,
            seed=seed or 0,
        )
    # data → latents (VAE encode once; the clip is fixed, run_tuning.py:282-287)
    with span("tune.load_clip", frames=n_frames):
        ds = SingleVideoDataset(
            video_path=train_data["video_path"],
            prompt=train_data["prompt"],
            width=int(train_data.get("width", 512)),
            height=int(train_data.get("height", 512)),
            n_sample_frames=n_frames,
            sample_start_idx=int(train_data.get("sample_start_idx", 0)),
            sample_frame_rate=int(train_data.get("sample_frame_rate", 1)),
        )
        video = jnp.asarray(ds.load())[None]  # (1, F, H, W, 3)
    with phase_timer("tune.vae_encode"):
        # one program, not an op-by-op walk of the encoder (each eager
        # op is its own compile on a cold start)
        latents = jax.jit(
            lambda vp, v, k: encode_video(bundle.vae, vp, v, k)
        )(bundle.vae_params, video.astype(dtype), ek)
        latents = jax.block_until_ready(latents.astype(jnp.float32))
    with span("tune.text_encode"):
        text_emb = encode_prompts(bundle, [train_data["prompt"]])
    device_mesh = None
    if mesh:
        from videop2p_tpu.parallel import latent_sharding

        # shard the bundle BEFORE TrainState.create so the partitioned
        # trainable/frozen trees (and the optimizer state initialized from
        # them) inherit the placements
        device_mesh = setup_mesh(bundle, mesh, n_frames)
        latents = jax.device_put(latents, latent_sharding(device_mesh))
    with span("tune.state_create"):
        state = TrainState.create(
            bundle.unet_params["params"], tx, tune_cfg.trainable_modules
        )
    noise_sched = DDPMScheduler.create_sd(prediction_type=prediction_type)
    unet_fn = make_unet_fn(bundle.unet)

    def program(s, k, n):
        return train_steps(
            unet_fn, tx, s, noise_sched, latents, text_emb, k,
            num_steps=n, dependent_sampler=sampler, telemetry=telemetry,
        )

    def validate(s, validation_data, output_dir, step, *, dependent_weights, key):
        _validate(
            bundle, s, latents, validation_data, output_dir, step,
            dependent_weights=dependent_weights, sampler=sampler,
            text_emb=text_emb, key=key,
        )

    def export(s, output_dir, step):
        save_pipeline(
            output_dir,
            bundle.unet.config,
            {"params": s.params},
            source_dir=bundle.source_dir,
            scheduler_config={
                "_class_name": "DDIMScheduler",
                "beta_start": 0.00085,
                "beta_end": 0.012,
                "beta_schedule": "scaled_linear",
                "clip_sample": False,
                "set_alpha_to_one": False,
                "steps_offset": 1,
            },
        )
        print(f"[tune] saved pipeline to {output_dir}")
        return {"pipeline_dir": output_dir}

    return _Tuned(state, program, lambda s: s, validate, export, device_mesh)


def _setup_token_model(
    model_family, model, train_data, tx, tune_cfg, *, dtype,
    gradient_checkpointing, tiny, seed, telemetry,
) -> _Tuned:
    """A token model on one document of token ids: no VAE, no text encoder,
    no validation edit; ``loss_steps`` on the next-token loss."""
    with span("tune.build_models"):
        bundle = build_token_model(
            model, model_family=model_family, dtype=dtype,
            gradient_checkpointing=gradient_checkpointing, tiny=tiny,
            seed=seed or 0,
        )
    # data → token ids (one document, the same every step)
    with span("tune.load_document", tokens=int(train_data["n_tokens"])):
        ids = jnp.asarray(TokenDocument(
            n_tokens=int(train_data["n_tokens"]),
            vocab_size=bundle.config.vocab_size,
            document_path=train_data.get("document_path"),
            document_seed=int(train_data.get("document_seed", 0)),
        ).load())[None]  # (1, T)
    with span("tune.state_create"):
        # frozen leaves stay in the checkpoint's dtype; the trainable
        # ones get a float32 master copy (and float32 moments)
        state = TrainState.create(
            bundle.params, tx, tune_cfg.trainable_modules,
            master_dtype=jnp.float32,
        )
        bundle.params = None  # the state owns the weights from here
        if not jax.tree.leaves(state.trainable):
            raise ValueError(
                f"trainable_modules {list(tune_cfg.trainable_modules)} match "
                "no leaf of the model"
            )
    step_loss = next_token_loss(bundle.loss_fn, ids)

    def program(s, k, n):
        return loss_steps(step_loss, tx, s, k, num_steps=n, telemetry=telemetry)

    def saved(s):
        # a checkpoint holds the tuned leaves and their optimizer state only:
        # the frozen share is gigabytes of the checkpoint's own weights
        return s.replace(frozen={})

    def export(s, output_dir, step):
        # the tuned leaves are the artifact: no pipeline directory exists
        # for this family
        with span("tune.checkpoint", step=step):
            path = save_checkpoint(output_dir, jax.device_get(saved(s)), step)
        print(f"[tune] saved the tuned leaves to {path}")
        return {"checkpoint": path}

    return _Tuned(state, program, saved, None, export)


def main(
    pretrained_model_path: str,
    output_dir: str,
    train_data: Dict[str, Any],
    validation_data: Optional[Dict[str, Any]] = None,
    learning_rate: float = 3e-5,
    train_batch_size: int = 1,
    max_train_steps: int = 500,
    checkpointing_steps: int = 1000,
    validation_steps: int = 500,
    trainable_modules=("attn1.to_q", "attn2.to_q", "attn_temp"),
    seed: Optional[int] = None,
    mixed_precision: str = "fp16",
    gradient_checkpointing: bool = True,
    gradient_accumulation_steps: int = 1,
    max_grad_norm: float = 1.0,
    lr_scheduler: str = "constant",
    lr_warmup_steps: int = 0,
    scale_lr: bool = False,
    resume_from_checkpoint: Optional[str] = None,
    prediction_type: str = "epsilon",
    # fork flags (run_tuning.py:401-412)
    dependent: bool = False,
    num_frames: int = 60,
    decay_rate: float = 0.1,
    window_size: int = 60,
    ar_sample: bool = False,
    ar_coeff: float = 0.1,
    eta: float = 0.0,
    dependent_weights: float = 0.0,
    # device mesh "dp,sp,tp" — shards the tuning step across chips: frames
    # over sp (ring attention at uncontrolled temporal sites), attention/FF
    # kernels over tp. Single-clip tuning needs dp=1.
    mesh: Optional[str] = None,
    # which model Stage 1 tunes (cli/common.MODEL_FAMILIES): the inflated
    # video UNet on a clip, or a token model on a document of token ids —
    # ``model`` is that family's configuration (models/deepseek.py,
    # models/granite_hybrid.py, models/cohere2_moe.py). The token
    # model has no VAE, no text encoder and no validation edit.
    model_family: str = "unet3d",
    model: Optional[Dict[str, Any]] = None,
    # extras (not in the reference)
    tiny: bool = False,
    log_every: int = 50,
    # train steps per device call (lax.scan chunk): one dispatch per
    # program, so a chunk amortizes the per-call host overhead over its
    # steps while a call stays well under a minute
    steps_per_call: int = 100,
    # observability (videop2p_tpu/obs): per-step loss + grad-norm telemetry
    # riding the train scan + a JSONL run ledger
    telemetry: bool = False,
    ledger: Optional[str] = None,
    # distributed observability (ISSUE 5, obs/comm.py): after training,
    # measure the cross-replica divergence of the tuned params over the
    # mesh axes they are replicated on — the invariant a desynced replica
    # breaks silently — and ledger it (divergence must be 0.0; COMM_RULES)
    device_telemetry: bool = False,
    # time-domain observability (ISSUE 6, obs/timing.py + obs/trace.py):
    # --latency accumulates per-dispatch (dispatch-return, blocked)
    # latencies of the train_steps program into bounded reservoirs →
    # execute_timing ledger events gated by TIMING_RULES;
    # --trace_analysis wraps the training loop in a jax.profiler capture
    # mined into a trace_analysis event by the stdlib xplane reader
    latency: bool = False,
    trace_analysis: bool = False,
    # --incidents DIR arms the incident plane (obs/incident.py): flight-
    # ring tee on the run ledger + crash/SIGUSR1 capture bundles
    incidents: Optional[str] = None,
    # automatic XLA cost/memory analysis of each instrumented program on
    # compile (program_analysis ledger events; obs/introspect.py)
    program_analysis: bool = True,
    **unused,
) -> str:
    del unused
    unet_family = check_model_family(model_family) == "unet3d"
    validation_data = validation_data or {}
    if mesh and not unet_family:
        raise NotImplementedError(
            f"model_family {model_family!r} runs one chip's share without "
            "any exchange between chips: no mesh path is built for it yet"
        )
    n_frames = int(train_data.get("n_sample_frames", 8))
    output_dir = output_dir + dependent_suffix(
        dependent=dependent, decay_rate=decay_rate, window_size=window_size,
        ar_sample=ar_sample, ar_coeff=ar_coeff, eta=eta,
        dependent_weights=dependent_weights,
    )
    # unified run record (videop2p_tpu/obs): spans, phases, compile events,
    # train metrics and telemetry land in one JSONL stream, line-flushed.
    # The flags→ledger wiring is shared with run_videop2p and the serving
    # engine (cli/common.make_run_ledger). Made first, so that the root span
    # finds it.
    run_ledger = make_run_ledger(
        os.path.join(output_dir, "run_ledger.jsonl"),
        ledger=ledger, mesh=mesh,
        meta={"cli": "run_tuning", "max_train_steps": max_train_steps},
        telemetry=telemetry, device_telemetry=device_telemetry,
        latency=latency, trace_analysis=trace_analysis,
        program_analysis=program_analysis, incidents=incidents,
    )
    # root span: what a user waits for before the second chunk starts. It is
    # closed by hand at the end of the first chunk's bookkeeping, in the
    # loop below; the `with` closes it where the run ends before that.
    with span("tune.setup") as setup_span:
        enable_compile_cache()
        os.makedirs(output_dir, exist_ok=True)
        with open(os.path.join(output_dir, "config.json"), "w") as f:
            json.dump({k: v for k, v in locals().items()
                       if isinstance(v, (str, int, float, bool, dict, list, tuple, type(None)))},
                      f, indent=2, default=str)

        sampler = None
        if dependent:
            if num_frames != n_frames:
                print(f"[tune] dependent sampler uses the clip's {n_frames} frames "
                      f"(--num_frames {num_frames} would not match the data)")
            sampler = DependentNoiseSampler.create(
                num_frames=n_frames, decay_rate=decay_rate,
                window_size=min(window_size, n_frames), ar_sample=ar_sample,
                ar_coeff=ar_coeff,
            )

        dtype = {"fp16": jnp.bfloat16, "bf16": jnp.bfloat16, "no": jnp.float32}[mixed_precision]
        key = jax.random.key(seed if seed is not None else 0)
        key, ek = jax.random.split(key)
        tune_cfg = TuneConfig(
            learning_rate=learning_rate,
            scale_lr=scale_lr,
            lr_scheduler=lr_scheduler,
            lr_warmup_steps=lr_warmup_steps,
            max_train_steps=max_train_steps,
            max_grad_norm=max_grad_norm,
            gradient_accumulation_steps=gradient_accumulation_steps,
            trainable_modules=tuple(trainable_modules),
            train_batch_size=train_batch_size,
        )
        tx = make_optimizer(tune_cfg)
        # the family's models, data, train state and step program
        if unet_family:
            tuned = _setup_unet(
                pretrained_model_path, train_data, tx, tune_cfg, ek,
                dtype=dtype, gradient_checkpointing=gradient_checkpointing,
                tiny=tiny, seed=seed, mesh=mesh, sampler=sampler,
                prediction_type=prediction_type, telemetry=telemetry,
            )
        else:
            tuned = _setup_token_model(
                model_family, model, train_data, tx, tune_cfg, dtype=dtype,
                gradient_checkpointing=gradient_checkpointing, tiny=tiny,
                seed=seed, telemetry=telemetry,
            )
        state, program, saved = tuned.state, tuned.program, tuned.saved
        tuned = tuned._replace(state=None)  # donated at the first call
        first_step = 0
        if resume_from_checkpoint:
            path = (
                latest_checkpoint(output_dir)
                if resume_from_checkpoint == "latest"
                else resume_from_checkpoint
            )
            if path:
                restored = restore_checkpoint(path, saved(state))
                # a checkpoint that holds no frozen leaves (the token
                # model's) keeps the ones just built
                state = (restored if jax.tree.leaves(restored.frozen)
                         else restored.replace(frozen=state.frozen))
                first_step = int(state.step)
                print(f"[tune] resumed from {path} at step {first_step}")
        # multiple steps per device call (lax.scan over the per-step keys): one
        # dispatch per program instead of one per step (train/tuner.py
        # train_steps)
        # the state (params + Adam moments) is donated: the carry tree would
        # otherwise be held twice (in + out) inside the program and copied —
        # nothing else reads bundle.unet_params after TrainState.create above
        steps_fn = instrumented_jit(
            program,
            program="train_steps",
            span_attrs=lambda s, k, n: {"steps": n},
            static_argnums=2,
            donate_argnums=(0,),
        )

        # per-step train_loss/lr tracker (the reference's accelerator.log /
        # TensorBoard trackers, run_tuning.py:234,337,377-378); with an active
        # ledger every logged step also becomes a ledger `metric` event
        lr_schedule = make_lr_schedule(tune_cfg)
        with span("tune.metrics_logger"):  # opens the TensorBoard event file
            metrics = MetricsLogger(output_dir, ledger=run_ledger)
        losses = []
        grad_norms = []  # telemetry mode only: per-step pre-clip global norm
        counters = []  # what the loss hands out beside itself, a dict a chunk

        def flush_losses(next_step):
            with span("tune.flush_losses") as flush_span:
                # one sync for the whole buffer (per-step float() would
                # serialize host dispatch against device compute)
                flat = np.asarray(jax.block_until_ready(jnp.concatenate(losses)))
                gflat = (np.asarray(jax.block_until_ready(jnp.concatenate(grad_norms)))
                         if grad_norms else None)
                cflat = {name: np.concatenate([np.asarray(c[name]) for c in counters])
                         for name in (counters[0] if counters else {})}
                flush_span.set(steps=len(flat))
                start = next_step - len(flat)
                for j, lv in enumerate(flat):
                    rec = {"train_loss": float(lv), "lr": float(lr_schedule(start + j))}
                    if gflat is not None:
                        rec["grad_norm"] = float(gflat[j])
                    rec.update({name: float(v[j]) for name, v in cflat.items()})
                    metrics.log(start + j + 1, rec)
                losses.clear()
                grad_norms.clear()
                counters.clear()
                return float(flat[-1])

        # chunks align with the periodic boundaries so per-step losses,
        # checkpoints and validation keep their exact cadence; a cadence of
        # 0/None disables that feature entirely
        import math

        steps_per_call = max(int(steps_per_call), 1)
        cadences = [p for p in (log_every, checkpointing_steps, validation_steps)
                    if p and p > 0]
        # distinct chunk lengths each compile their own scan program
        # (static_argnums) — round steps_per_call down to divide the cadences'
        # gcd when that keeps a useful chunk, so the loop reuses ONE executable
        g = math.gcd(*cadences) if cadences else steps_per_call
        if g > 1 and steps_per_call % g and g % steps_per_call:
            aligned = math.gcd(steps_per_call, g)
            if aligned >= 5:
                print(
                    f"[tune] steps_per_call {steps_per_call} → {aligned} to align "
                    f"with the log/checkpoint/validation cadences (gcd {g}); "
                    "smaller chunks amortize the per-call dispatch overhead less "
                    "— align the cadences to a multiple of steps_per_call to "
                    "keep the full chunk"
                )
                steps_per_call = aligned
        t0 = time.perf_counter()
        # per-step noise keys derive from (this run key, absolute step) inside
        # train_steps — logging/checkpoint cadence and resume points cannot
        # change the training noise sequence
        key, train_key = jax.random.split(key)
        i = first_step
        traced_chunk = False
        preempted = False
        restore_signals = _install_preempt_handlers()
        while i < max_train_steps:
            nxt = min(
                [max_train_steps, i + steps_per_call]
                + [(i // p + 1) * p for p in cadences]
            )
            # --trace_analysis: capture ONE post-compile chunk (the second —
            # the first is dominated by the scan compile) and mine it into a
            # trace_analysis ledger event; tracing every chunk would write
            # gigabytes of xplane protos for a long tune
            do_trace = trace_analysis and not traced_chunk and i > first_step
            if do_trace:
                from videop2p_tpu.obs.trace import trace_window

                chunk_ctx = trace_window("train_steps_chunk")
            else:
                chunk_ctx = contextlib.nullcontext()
            with chunk_ctx:
                out = steps_fn(state, train_key, nxt - i)
                if do_trace:
                    jax.block_until_ready(out)  # the capture must hold the work
                    traced_chunk = True
            state, chunk_losses = out[0], out[1]
            if telemetry:
                grad_norms.append(out[2])
            if isinstance(out[-1], dict):
                # the scalars a step are logged; whatever else the loss hands
                # out (arrays a step) is for whoever wrapped the program
                counters.append({name: v for name, v in out[-1].items()
                                 if getattr(v, "ndim", None) == 1})
            del out  # nothing of a chunk's outputs outlives its bookkeeping
            losses.append(chunk_losses)  # device-side; no per-chunk host sync
            first_chunk = i == first_step
            i = nxt
            if _PREEMPT_EVENT.is_set():
                # SIGTERM/SIGINT landed: save the final checkpoint at this
                # chunk boundary and exit cleanly (skip validation/export —
                # the resumed run redoes them); handled after the loop
                preempted = True
                break
            if (log_every and i % log_every == 0) or i == max_train_steps or first_chunk:
                loss = flush_losses(i)
                rate = (i - first_step) / max(time.perf_counter() - t0, 1e-9)
                print(f"[tune] step {i}/{max_train_steps} loss={loss:.4f} "
                      f"({rate:.2f} it/s)")
            if checkpointing_steps and i % checkpointing_steps == 0:
                with span("tune.checkpoint", step=i):
                    save_checkpoint(output_dir, jax.device_get(saved(state)), i)
            if tuned.validate and (
                (validation_steps and i % validation_steps == 0) or i == max_train_steps
            ):
                with span("tune.validate", step=i):
                    tuned.validate(
                        state, validation_data, output_dir, i,
                        dependent_weights=dependent_weights, key=key,
                    )
            setup_span.end()  # the first chunk's bookkeeping is done (later: no-op)
        restore_signals()
        if preempted:
            if losses:
                flush_losses(i)
            metrics.close()
            with span("tune.checkpoint", step=i):
                ckpt_path = save_checkpoint(
                    output_dir, jax.device_get(saved(state)), i)
            print(f"[tune] preempted at step {i} — checkpoint saved to "
                  f"{ckpt_path}; resume with resume_from_checkpoint: latest")
            if run_ledger is not None:
                run_ledger.event("preempted", step=i, checkpoint=ckpt_path)
                run_ledger.close()
            return output_dir
        if losses:  # flush the tail of the buffer
            flush_losses(max_train_steps)
        metrics.close()
        if run_ledger is not None:
            run_ledger.memory_snapshot(note="after_training")
        if device_telemetry and mesh:
            # the tuned params must be IDENTICAL on every mesh replica (dp=1
            # single-clip tuning replicates non-tensor-parallel params over the
            # whole mesh); a nonzero divergence means a replica desynced — the
            # ledger event joins the zero-noise-floor COMM_RULES gate
            from videop2p_tpu.obs.comm import tree_replica_divergence

            device_mesh = tuned.mesh
            div_axes = tuple(
                a for a in device_mesh.axis_names if device_mesh.shape[a] > 1
            )
            if div_axes:
                div = float(tree_replica_divergence(
                    state.params, device_mesh, axes=div_axes
                ))
                if run_ledger is not None:
                    run_ledger.divergence("params_after_training", div,
                                          axes=list(div_axes))
                print(f"[tune] param replica divergence over {div_axes}: {div}"
                      + ("  <-- REPLICAS DIVERGED (must be 0.0)" if div else ""))

        artifacts = tuned.export(state, output_dir, i)
        if run_ledger is not None:
            run_ledger.event("artifacts", **artifacts)
            run_ledger.close()
            print(f"[tune] run ledger: {run_ledger.path}")
        return output_dir


def run_distillation(
    pipeline_dir: str,
    train_data: Dict[str, Any],
    *,
    distill_steps: int,
    distill_grid: int = 50,
    distill_lr: float = 1e-4,
    distill_ema: float = 0.95,
    distill_boundary_weight: float = 1.0,
    tiny: bool = False,
    seed: Optional[int] = None,
    steps_per_call: int = 50,
) -> str:
    """Consistency-distill the few-step student from a tuned pipeline dir
    (ISSUE 16 — train/distill.py): the tuned UNet is the frozen teacher,
    the student re-trains the tuner's parameter subset plus the
    time-conditioning head against the self-consistency objective on the
    SAME clip latents the tuning used. Writes the servable student
    artifact to ``<pipeline_dir>/student/checkpoint-<step>`` — the path
    ``cli.serve --student_ckpt`` and ``ProgramSpec.student_ckpt`` take.
    Returns the checkpoint path."""
    from videop2p_tpu.train import (
        DistillConfig,
        DistillState,
        init_time_head,
        make_distill_optimizer,
        save_student,
    )
    from videop2p_tpu.train import distill_steps as distill_scan

    n_frames = int(train_data.get("n_sample_frames", 8))
    bundle = build_models(
        pipeline_dir, dtype=jnp.float32, frame_attention="chunked",
        tiny=tiny, seed=seed or 0,
    )
    ds = SingleVideoDataset(
        video_path=train_data["video_path"],
        prompt=train_data["prompt"],
        width=int(train_data.get("width", 512)),
        height=int(train_data.get("height", 512)),
        n_sample_frames=n_frames,
        sample_start_idx=int(train_data.get("sample_start_idx", 0)),
        sample_frame_rate=int(train_data.get("sample_frame_rate", 1)),
    )
    video = jnp.asarray(ds.load())[None]
    key = jax.random.key(seed if seed is not None else 0)
    key, ek, hk = jax.random.split(key, 3)
    with phase_timer("vae_encode"):
        latents = encode_video(
            bundle.vae, bundle.vae_params, video.astype(jnp.float32), ek
        )
        latents = jax.block_until_ready(latents.astype(jnp.float32))
    text_emb = encode_prompts(bundle, [train_data["prompt"]])

    cfg = DistillConfig(
        learning_rate=distill_lr,
        max_train_steps=distill_steps,
        distill_grid=distill_grid,
        ema_decay=distill_ema,
        boundary_weight=distill_boundary_weight,
    )
    tx = make_distill_optimizer(cfg)
    head = init_time_head(hk, bundle.unet.config)
    state = DistillState.create(
        bundle.unet_params["params"], head, tx, cfg.trainable_modules
    )
    sched = bundle.make_scheduler()  # the DDIM grid the student walks
    unet_fn = make_unet_fn(bundle.unet)
    steps_fn = instrumented_jit(
        lambda s, k, n: distill_scan(
            unet_fn, tx, s, sched, latents, text_emb, k,
            num_steps=n, cfg=cfg,
        ),
        program="distill_steps",
        static_argnums=2,
        donate_argnums=(0,),
    )
    key, dk = jax.random.split(key)
    steps_per_call = max(int(steps_per_call), 1)
    i, t0 = 0, time.perf_counter()
    while i < distill_steps:
        n = min(steps_per_call, distill_steps - i)
        state, chunk_losses = steps_fn(state, dk, n)
        i += n
        loss = float(np.asarray(jax.block_until_ready(chunk_losses))[-1])
        rate = i / max(time.perf_counter() - t0, 1e-9)
        print(f"[distill] step {i}/{distill_steps} loss={loss:.5f} "
              f"({rate:.2f} it/s)")
    path = save_student(
        os.path.join(pipeline_dir, "student"), jax.device_get(state), i
    )
    print(f"[distill] saved student to {path}")
    return path


def _validate(
    bundle, state, latents, validation_data, output_dir, step, *,
    dependent_weights, sampler, text_emb, key,
):
    """Inversion + sampling validation (run_tuning.py:346-375): DDIM-invert
    the training latents, store them, sample each validation prompt from the
    inverted noise, write a GIF grid."""
    num_inv = int(validation_data.get("num_inv_steps", 50))
    num_steps = int(validation_data.get("num_inference_steps", 50))
    guidance = float(validation_data.get("guidance_scale", 12.5))
    use_inv = bool(validation_data.get("use_inv_latent", True))
    prompts: List[str] = list(validation_data.get("prompts", []))
    unet_fn = make_unet_fn(bundle.unet)
    sched = DDIMScheduler.create_sd()
    params = {"params": state.params}

    with phase_timer("validation"):
        if use_inv:
            traj = ddim_inversion(
                unet_fn, params, sched, latents, text_emb,
                num_inference_steps=num_inv,
                dependent_weight=dependent_weights,
                dependent_sampler=sampler if dependent_weights > 0 else None,
                key=key,
            )
            x_t = traj[-1]
            inv_dir = os.path.join(output_dir, "inv_latents")
            os.makedirs(inv_dir, exist_ok=True)
            np.save(os.path.join(inv_dir, f"ddim_latent-{step}.npy"),
                    np.asarray(jax.device_get(x_t)))
        else:
            x_t = jax.random.normal(key, latents.shape, latents.dtype)

        # one compile shared by every validation prompt (same shapes)
        sample_fn = jax.jit(
            lambda p, xt, c, u: edit_sample(
                unet_fn, p, sched, xt, c, u,
                num_inference_steps=num_steps, guidance_scale=guidance,
            )
        )
        uncond = encode_prompts(bundle, [""])[0]
        videos = []
        for prompt in prompts:
            cond = encode_prompts(bundle, [prompt])
            out = sample_fn(params, x_t, cond, uncond)
            frames = decode_video(bundle.vae, bundle.vae_params, out.astype(jnp.float32))
            videos.append(np.asarray(jax.device_get((frames + 1) / 2))[0])
    if videos:
        path = os.path.join(output_dir, "samples", f"sample-{step}.gif")
        save_videos_grid(np.stack(videos), path)
        print(f"[tune] validation saved {path}")


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, required=True)
    parser.add_argument("--tiny", action="store_true",
                        help="random-init tiny models (weightless smoke mode)")
    parser.add_argument("--mesh", type=str, default=None,
                        help="device mesh 1,sp,tp (frames/tensor sharding)")
    # consistency distillation of the few-step student (ISSUE 16 —
    # train/distill.py; runs AFTER tuning, teacher = the tuned pipeline)
    parser.add_argument("--distill_steps", type=int, default=0,
                        help="consistency-distillation steps to run after "
                             "tuning (0 = off); writes the servable student "
                             "to <output_dir>/student/checkpoint-<N>")
    parser.add_argument("--distill_grid", type=int, default=50,
                        help="DDIM grid points the self-consistency chain "
                             "walks (the teacher's solver discretization)")
    parser.add_argument("--distill_lr", type=float, default=1e-4,
                        help="student learning rate (AdamW via the tuner's "
                             "partitioned optimizer)")
    parser.add_argument("--distill_ema", type=float, default=0.95,
                        help="EMA decay of the consistency target network")
    parser.add_argument("--distill_boundary_weight", type=float, default=1.0,
                        help="loss weight of the boundary term (final grid "
                             "point, target = the data x0)")
    add_dependent_args(parser)
    add_obs_args(parser)
    args = parser.parse_args()
    # multi-host: join the process group before any device use (no-op on a
    # single host; see parallel/distributed.py)
    from videop2p_tpu.parallel import initialize_distributed

    initialize_distributed()
    if args.attn_maps or args.quality or args.report:
        # the flags live in the shared add_obs_args surface; the semantic
        # layer instruments the EDIT pipelines (run_videop2p)
        print("[tune] --attn_maps/--quality/--report are Stage-2 (editing) "
              "knobs — ignored by the tuning CLI")
    cfg = load_config(args.config)
    args.mesh = args.mesh or cfg.pop("mesh", None)
    out_dir = main(
        **cfg,
        mesh=args.mesh,
        dependent=args.dependent,
        num_frames=args.num_frames,
        decay_rate=args.decay_rate,
        window_size=args.window_size,
        ar_sample=args.ar_sample,
        ar_coeff=args.ar_coeff,
        eta=args.eta,
        dependent_weights=args.dependent_weights,
        tiny=args.tiny,
        telemetry=args.telemetry,
        ledger=args.ledger,
        program_analysis=not args.no_program_analysis,
        device_telemetry=args.device_telemetry,
        latency=args.latency,
        trace_analysis=args.trace_analysis,
        incidents=args.incidents,
    )
    if args.distill_steps > 0:
        run_distillation(
            out_dir, cfg["train_data"],
            distill_steps=args.distill_steps,
            distill_grid=args.distill_grid,
            distill_lr=args.distill_lr,
            distill_ema=args.distill_ema,
            distill_boundary_weight=args.distill_boundary_weight,
            tiny=args.tiny,
            seed=cfg.get("seed"),
        )
