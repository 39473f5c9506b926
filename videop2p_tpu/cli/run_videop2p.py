"""Stage-2 attention-controlled editing entry point.

TPU-native re-design of /root/reference/run_videop2p.py: same YAML schema
(configs/rabbit-jump-p2p.yaml) and flag surface. Flow (run_videop2p.py:42-701):
load the Stage-1 pipeline dir (with the fork's dependent-suffix path
contract), load + VAE-encode the frame sequence, DDIM-invert it, optionally
run null-text optimization (full mode), build the controller from the edit
spec, run the controlled CFG denoise, and write two GIFs — the inversion
reconstruction stream and the edited stream (run_videop2p.py:692-701).

Run:  python -m videop2p_tpu.cli.run_videop2p --config configs/rabbit-jump-p2p.yaml --fast
"""

from __future__ import annotations

import argparse
import contextlib
import os
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from videop2p_tpu.cli.common import (
    add_dependent_args,
    add_null_text_args,
    add_obs_args,
    load_config,
    make_run_ledger,
    resolve_pipeline_dir,
    enable_compile_cache,
)
from videop2p_tpu.core import DependentNoiseSampler
from videop2p_tpu.obs import instrumented_jit, program_label
from videop2p_tpu.obs.spans import entry_imported
from videop2p_tpu.data import load_frame_sequence
from videop2p_tpu.models import decode_video
from videop2p_tpu.pipelines import (
    ddim_inversion,
    edit_sample,
    make_unet_fn,
    null_text_optimization,
    null_text_optimization_fused,
)
from videop2p_tpu.utils.profiling import phase_timer
from videop2p_tpu.utils.video_io import save_video_gif

entry_imported()  # the end of the span `process.import`
# module-level working-point constants (run_videop2p.py:32-40)
NUM_DDIM_STEPS = 50
GUIDANCE_SCALE = 7.5
MASK_TH = (0.3, 0.3)


class EditResult(tuple):
    """What :func:`main` returns: the ``(inversion_gif, edit_gif)`` pair,
    carrying for in-process callers (``chip_smoke.py``) ``branch`` ("cached"
    or "live"), ``src_err`` (cached branch: max |replayed source − encoded
    clip|, else None), ``videos`` ((P, F, H, W, 3) in [0, 1]), ``latents``
    (the edited (P, F, h, w, C) latents, float32) and ``latent_devices``."""

    def __new__(cls, inversion_gif: str, edit_gif: str, **fields):
        self = super().__new__(cls, (inversion_gif, edit_gif))
        self.__dict__.update(fields)
        return self


def _word_token_records(prompts: Sequence[str], tokenizer) -> list:
    """Word → token-position records for every prompt (the report's key
    for slicing per-word heatmaps out of the per-token capture)."""
    from videop2p_tpu.control.schedules import get_word_inds

    recs, seen = [], set()
    for pi, text in enumerate(prompts):
        for word in text.split():
            if (pi, word) in seen:
                continue
            seen.add((pi, word))
            toks = get_word_inds(text, word, tokenizer)
            if len(toks):
                recs.append({"prompt": pi, "word": word,
                             "tokens": [int(t) for t in toks]})
    return recs


def _ledger_device_stats(run_ledger, program, dev_stats, probe) -> None:
    """Summarize one scan's device-probe channels into a
    ``device_telemetry`` ledger event (+ a console warning when the
    replicas diverged — divergence joins the zero-noise-floor COMM_RULES
    gate via obs/history.py)."""
    from videop2p_tpu.obs import summarize_device_stats

    rec = summarize_device_stats(dev_stats, probe.device_ids)
    rec["divergence_axes"] = list(probe.divergence_axes)
    if run_ledger is not None:
        run_ledger.device_telemetry(program, rec)
    div = rec.get("divergence_max", 0.0)
    line = (f"[p2p] device telemetry ({program}): {rec.get('devices')} "
            f"devices, divergence_max={div}")
    if div:
        line += "  <-- REPLICAS DIVERGED (must be 0.0)"
    print(line)


def _semantic_obs(
    run_ledger,
    *,
    output_folder: str,
    save_name: str,
    suffix: str,
    prompts: Sequence[str],
    tokenizer,
    attn_records: Dict,
    stream_map: Dict,
    quality: bool,
    report: bool,
    source01: np.ndarray,
    videos: np.ndarray,
) -> Optional[str]:
    """Post-decode semantic observability: the ``.npz`` sidecar, the
    ``attn_maps``/``quality`` ledger events, cross-run regression verdicts
    (quality rules included), and the self-contained HTML report. Returns
    the report path when one was written."""
    from videop2p_tpu.obs.attention import save_obs_sidecar, summarize_attn_record

    sidecar_path = os.path.join(
        output_folder, f"obs_sidecar_{save_name}{suffix}.npz"
    )
    sidecar: Dict[str, np.ndarray] = {}
    word_recs = _word_token_records(prompts, tokenizer)
    summaries = {}
    for scope, rec in attn_records.items():
        sidecar[f"attn_{scope}/cross_heat"] = np.asarray(rec["cross_heat"])
        for site, curve in sorted(rec.get("entropy", {}).items()):
            sidecar[f"attn_{scope}/entropy/{site}"] = np.asarray(curve)
        for k in ("mask_cov", "mask_heat", "blend_active"):
            if k in rec:
                sidecar[f"attn_{scope}/{k}"] = np.asarray(rec[k])
        summaries[scope] = summarize_attn_record(rec)

    # reference frames for the report's overlays, bounded at 128px
    stride = max(1, int(videos.shape[-3]) // 128)
    to_u8 = lambda v: (np.clip(v[:, ::stride, ::stride], 0, 1) * 255).astype(np.uint8)  # noqa: E731
    sidecar["frames/source"] = to_u8(np.asarray(source01))
    sidecar["frames/recon"] = to_u8(videos[0])
    sidecar["frames/edit"] = to_u8(videos[1])

    quality_summary = None
    if quality:
        from videop2p_tpu.obs.quality import edit_quality_record

        mask = None
        mh = attn_records.get("edit", {}).get("mask_heat")
        if mh is not None:
            mh = np.asarray(mh)  # (T, P, F, rh, rw), source stream first
            if mh.ndim == 5 and mh.shape[1] >= 2:
                m = np.clip(mh[-1, 1], 0.0, 1.0)  # final step, first edit
                F, H, W = videos.shape[1], videos.shape[2], videos.shape[3]
                yi = (np.arange(H) * m.shape[1] // max(H, 1)).clip(0, m.shape[1] - 1)
                xi = (np.arange(W) * m.shape[2] // max(W, 1)).clip(0, m.shape[2] - 1)
                mask = m[:F][:, yi][:, :, xi]
        quality_summary, curves = edit_quality_record(
            np.asarray(source01), videos[0], videos[1], mask=mask
        )
        for k, v in curves.items():
            sidecar[f"quality/{k}"] = v

    save_obs_sidecar(sidecar_path, sidecar)

    for scope, summary in summaries.items():
        streams = stream_map.get(scope, [])
        run_ledger.event(
            "attn_maps", scope=scope, program=f"attn_{scope}",
            sidecar=sidecar_path, streams=streams,
            words=[w for w in word_recs if w["prompt"] in streams],
            **summary,
        )
    if quality_summary is not None:
        run_ledger.event("quality", program="edit_quality",
                         sidecar=sidecar_path, **quality_summary)
        print("[p2p] quality: " + ", ".join(
            f"{k}={v}" for k, v in quality_summary.items()))

    # cross-run regression verdicts (PR-3 engine + the quality rules):
    # the ledger file appends across invocations, so a repeat run has its
    # baseline in the same file — best-effort, never takes the run down
    try:
        from videop2p_tpu.obs import history as _history
        from videop2p_tpu.obs.ledger import read_ledger

        recs = [_history.extract_run(r)
                for r in _history.split_runs(read_ledger(run_ledger.path))]
        if len(recs) >= 2:
            cur = recs[-1]
            base = _history.RunHistory(recs[:-1]).baseline_for(cur) or recs[-2]
            res = _history.evaluate_rules(base, cur)
            run_ledger.event("regression_verdicts",
                             baseline_run_id=base.get("run_id"), **res)
            if not res["pass"]:
                print(f"[p2p] REGRESSIONS vs run {base.get('run_id')}: "
                      + ", ".join(v["rule"] for v in res["regressions"]))
    except Exception as e:  # noqa: BLE001 — observability never kills a run
        print(f"[p2p] regression verdicts skipped: {e}")

    report_path = None
    if report:
        from videop2p_tpu.obs.report import write_report

        report_path = write_report(
            run_ledger.path,
            os.path.join(output_folder, f"report_{save_name}{suffix}.html"),
            sidecar_path,
        )
        print(f"[p2p] edit report: {report_path}")
    return report_path


def main(
    pretrained_model_path: str,
    image_path: str,
    prompt: str,
    prompts: Sequence[str],
    save_name: str,
    is_word_swap: bool,
    eq_params: Optional[Dict] = None,
    blend_word: Optional[Sequence[str]] = None,
    cross_replace_steps: float = 0.2,
    self_replace_steps: float = 0.5,
    video_len: int = 8,
    fast: bool = False,
    mixed_precision: str = "fp32",
    # fork flags (run_videop2p.py:708-720)
    dependent: bool = False,
    dependent_p2p: bool = False,
    num_frames: int = 60,
    decay_rate: float = 0.1,
    window_size: int = 60,
    ar_sample: bool = False,
    ar_coeff: float = 0.1,
    eta: float = 0.0,
    dependent_weights: float = 0.0,
    # per-frame text-embedding mode (pipeline_tuneavideo.py:341,366-367)
    multi: bool = False,
    # device mesh "dp,sp,tp" — shards the edit across chips: frames over sp
    # (sequence parallel, ring attention on uncontrolled temporal sites),
    # attention/FF kernels over tp. Single-video Stage-2 needs dp=1.
    mesh: Optional[str] = None,
    # extras (not in the reference)
    tiny: bool = False,
    width: int = 512,
    num_inner_steps: int = 10,
    # null-text inner-loop precision: "mixed" runs the optimization's UNet
    # forwards in bf16 (a bf16-compute clone of the UNet over the same
    # params) with fp32 scheduler/Adam/loss islands (pipelines/inversion.py)
    null_text_precision: str = "fp32",
    # how the per-step uncond embedding is produced (pipelines/inversion.py):
    # "optimize" = the reference's per-step inner Adam loop; "amortized" =
    # closed-form negative-prompt-inversion substitute (zero inner Adam
    # steps — the structural attack on the 91%-of-e2e null-text phase);
    # "hybrid" = amortized seed + K<=3 refinement steps batched jointly
    # across all outer steps. Parity gated by the quality rules.
    null_text_mode: str = "optimize",
    # 0 = the fused single-dispatch donated-trajectory program;
    # N>0 = N-step host-dispatched chunks (execution-watchdog fallback)
    null_text_chunk: int = 0,
    seed: int = 0,
    # cached-source fast mode (pipelines/cached.py): drop the source stream
    # from the edit batch and replay it exactly from the inversion trajectory;
    # applies in --fast with eta=0 (sharded meshes included — GSPMD shards
    # the capture trees over frames; tests/test_parallel.py pins
    # sharded==unsharded), else falls back live
    cached_source: bool = True,
    # per-UNet-call cost levers (ISSUE 15). quant_mode quantizes the UNet
    # weights at load (models/convert.quantize_unet_params — int8 storage,
    # per-output-channel scales, dequantized inside the traced program);
    # reuse_schedule ("uniform:K" / "custom:<p0,...>") enables cross-step
    # deep-feature reuse in the cached edit scan (pipelines/reuse.py) and
    # requires the cached fast path. Both "off" by default — the off paths
    # are pinned bit-exact.
    quant_mode: str = "off",
    reuse_schedule: str = "off",
    # persist/reuse inversion products under the results dir so a repeat edit
    # of the same clip skips DDIM inversion and null-text entirely (the
    # reference's commented-out intent, run_videop2p.py:663-673)
    reuse_inversion: bool = True,
    # shared content-addressed root for those persisted products
    # (serve/store.py disk layer): sweeps and repeat invocations across
    # DIFFERENT output dirs amortize one inversion per clip. Default (None)
    # keeps the per-results-dir layout.
    inv_store: Optional[str] = None,
    # observability (videop2p_tpu/obs): in-program telemetry riding the
    # fused scans + a JSONL run ledger (phases, compile events, memory)
    telemetry: bool = False,
    ledger: Optional[str] = None,
    # semantic observability (ISSUE 4): per-step cross-attention capture
    # riding the same fused scans (obs/attention.py), post-decode edit-
    # quality metrics (obs/quality.py), and the self-contained HTML run
    # report (obs/report.py / tools/edit_report.py). Any of them implies
    # a run ledger (default path) — the events are the report's input.
    attn_maps: bool = False,
    quality: bool = False,
    report: bool = False,
    # distributed observability (ISSUE 5, obs/comm.py): a shard_map probe
    # riding the fused edit scan records per-device latent stats and a
    # cross-replica divergence scalar (device_telemetry ledger events —
    # divergence must be 0.0, gated by the zero-noise-floor COMM_RULES);
    # requires --mesh. comm_analysis events (collective counts/bytes) come
    # free with program_analysis on sharded programs.
    device_telemetry: bool = False,
    # time-domain observability (ISSUE 6): --latency accumulates every
    # instrumented dispatch's (dispatch-return, block-until-ready)
    # latencies into bounded per-program reservoirs (obs/timing.py),
    # flushed as execute_timing ledger events and gated by TIMING_RULES;
    # --trace_analysis wraps the main edit program in a jax.profiler
    # capture mined by the stdlib xplane reader (obs/trace.py) into a
    # trace_analysis event (+ .npz sidecar) with the compute/collective
    # overlap fraction. Both imply a run ledger; both off paths are
    # bit-exact (host-side measurement only).
    latency: bool = False,
    trace_analysis: bool = False,
    # --incidents DIR arms the incident plane (obs/incident.py): flight-
    # ring tee on the run ledger + crash/SIGUSR1 capture bundles
    incidents: Optional[str] = None,
    # automatic XLA cost/memory analysis of each instrumented program on
    # compile (program_analysis ledger events; obs/introspect.py) — the
    # per-program peak-HBM estimate the memory snapshots are checked
    # against, and what tools/obs_diff.py regresses across runs
    program_analysis: bool = True,
    **unused,
) -> EditResult:
    """Returns the (inversion_gif, edit_gif) paths it wrote, as an
    :class:`EditResult`."""
    del unused
    enable_compile_cache()
    if not program_analysis:
        os.environ["VIDEOP2P_OBS_NO_ANALYSIS"] = "1"
    if tiny and width == 512:
        # the tiny VAE downsamples 2×, not 8× — keep latents at the tiny
        # UNet's 8×8 working point so smoke runs stay small
        width = 16
    # Stage-1 ↔ Stage-2 path contract: the tuning run mangled its output dir
    # with the dependent hyperparameters (run_videop2p.py:74-78); results land
    # inside the checkpoint dir under results_dp{dependent_p2p} (:79).
    # Already-suffixed dirs (e.g. from the demo UI's picker) pass through.
    pretrained_model_path = resolve_pipeline_dir(
        pretrained_model_path,
        dependent=dependent, decay_rate=decay_rate, window_size=window_size,
        ar_sample=ar_sample, ar_coeff=ar_coeff, eta=eta,
        dependent_weights=dependent_weights,
    )
    output_folder = os.path.join(pretrained_model_path, f"results_dp{dependent_p2p}")
    suffix = "_fast" if fast else ""
    inversion_gif = os.path.join(output_folder, f"inversion{suffix}.gif")
    edit_gif = os.path.join(output_folder, f"{save_name}{suffix}.gif")
    os.makedirs(output_folder, exist_ok=True)

    # unified run record: every phase_timer region, XLA compile, decoded
    # telemetry summary and memory snapshot below lands in ONE JSONL stream
    # (events are line-flushed, so a killed run keeps what it measured).
    # The flags→ledger wiring is shared with run_tuning and the serving
    # engine (cli/common.make_run_ledger).
    run_ledger = make_run_ledger(
        os.path.join(output_folder, "run_ledger.jsonl"),
        ledger=ledger, mesh=mesh,
        meta={"cli": "run_videop2p", "fast": fast, "save_name": save_name,
              "prompt": prompt, "prompts": list(prompts),
              "null_text_precision": null_text_precision,
              "null_text_mode": null_text_mode},
        telemetry=telemetry, attn_maps=attn_maps, quality=quality,
        report=report, device_telemetry=device_telemetry, latency=latency,
        trace_analysis=trace_analysis, incidents=incidents,
    )

    def maybe_trace(window_name: str):
        """--trace_analysis: a mined jax.profiler capture around the
        named program region; a no-op context otherwise."""
        if trace_analysis:
            from videop2p_tpu.obs.trace import trace_window

            return trace_window(window_name)
        return contextlib.nullcontext()

    sampler = None
    if dependent_p2p or (dependent and eta > 0):
        sampler = DependentNoiseSampler.create(
            num_frames=video_len, decay_rate=decay_rate,
            window_size=min(window_size, video_len), ar_sample=ar_sample,
            ar_coeff=ar_coeff,
        )

    # model assembly, scheduler and the shared instrumented programs now
    # come from ONE ProgramSet (serve/programs.py) — the same object the
    # serving engine holds warm, so the program this CLI dispatches IS the
    # program the server batches. mixed_precision sets the model compute
    # dtype (the reference keeps the Stage-2 UNet fp32 — the fp32 default
    # here matches that); scheduler/latent math stays fp32 in every mode,
    # which is what carries inversion fidelity and the cached replay's
    # exactness. Full mode differentiates through the UNet (null-text
    # optimization); per-block remat keeps that backward inside one chip's
    # HBM (gradient_checkpointing=not fast).
    from videop2p_tpu.serve.programs import ProgramSet, ProgramSpec

    from videop2p_tpu.pipelines.reuse import validate_reuse_schedule

    reuse_schedule = validate_reuse_schedule(reuse_schedule, NUM_DDIM_STEPS)
    if reuse_schedule != "off" and not (cached_source and fast and eta == 0):
        raise ValueError(
            "reuse_schedule is a cached-fast-path knob: it needs --fast with "
            "eta=0 and cached_source (the deep-feature cache rides the fused "
            "edit scan)"
        )
    if quant_mode != "off" and not fast:
        raise ValueError(
            "quant_mode is an INFERENCE knob: full mode differentiates "
            "through the UNet (null-text optimization) and must see the "
            "full-precision weights — run it with --fast"
        )
    program_set = ProgramSet(ProgramSpec(
        checkpoint=pretrained_model_path, width=width, video_len=video_len,
        steps=NUM_DDIM_STEPS, guidance_scale=GUIDANCE_SCALE, tiny=tiny,
        mixed_precision=mixed_precision, seed=seed, mesh=mesh,
        gradient_checkpointing=not fast,
        quant_mode=quant_mode, reuse_schedule=reuse_schedule,
    ))
    bundle, dtype = program_set.bundle, program_set.dtype
    device_mesh = program_set.mesh

    # the per-device probe needs a mesh to shard_map over; single-device
    # runs have no replicas to diverge, so the flag degrades to a note
    device_probe = None
    if device_telemetry:
        if device_mesh is not None:
            from videop2p_tpu.obs import make_device_probe

            device_probe = make_device_probe(device_mesh)
            print(f"[p2p] device telemetry: probing {device_mesh.size} "
                  f"devices, divergence over {device_probe.divergence_axes}")
        else:
            print("[p2p] --device_telemetry needs --mesh — single-device "
                  "runs have no replicas to probe; flag ignored")

    unet_fn = program_set.unet_fn
    params = bundle.unet_params
    # the tuned pipeline's own scheduler config (incl. the steps_offset: 1 the
    # Stage-1 export writes), not hardcoded SD defaults (run_videop2p.py:101-114)
    sched = program_set.scheduler
    key = jax.random.key(seed)

    # ---- load + encode the video ----------------------------------------
    frames = load_frame_sequence(image_path, size=width, num_frames=video_len)
    video = program_set.frames_to_video(frames)  # (1,F,H,W,3) in [-1,1]
    with phase_timer("vae_encode"):
        # posterior mean, not a sample — inversion fidelity
        # (image2latent_video, run_videop2p.py:530-537); one jitted dispatch
        # through the shared instrumented vae_encode program
        latents = jax.block_until_ready(program_set.encode(video, key))
    if device_mesh is not None:
        from videop2p_tpu.parallel import latent_sharding

        # frames ride the sp axis; the inversion/edit jits below then compute
        # sequence-parallel with XLA-inserted collectives over ICI
        latents = jax.device_put(latents, latent_sharding(device_mesh))

    cond_src = program_set.encode_prompts([prompt])
    cond_all = program_set.encode_prompts(list(prompts))
    uncond = program_set.encode_prompts([""])[0]
    if multi:
        # per-frame conditioning: repeat each prompt embedding across frames
        # (the reference's `repeat(text_embeddings, 'b n c -> (b f) n c')`,
        # pipeline_tuneavideo.py:366-367); downstream consumers may then vary
        # embeddings per frame
        cond_all = jnp.repeat(cond_all[:, None], video_len, axis=1)

    # ---- controller (host-side; needed before inversion for the cached-
    # source capture windows) — shared construction with the serving
    # engine (the config's blend_word 2-list becomes ((src,), (edit,)),
    # run_videop2p.py:87-88)
    ctx = program_set.controller(
        list(prompts),
        is_word_swap=bool(is_word_swap),
        cross_replace_steps=cross_replace_steps,
        self_replace_steps=self_replace_steps,
        blend_word=blend_word,
        eq_params=eq_params,
        mask_th=MASK_TH,
    )

    # ---- DDIM inversion (+ null-text in full mode) ----------------------
    dep_w = dependent_weights if dependent_p2p else 0.0

    use_cached = cached_source and fast and eta == 0

    # persisted-products lookup: on a hit the inversion walk (and, when
    # present, the null-text optimization) is skipped. NOT consulted when
    # the cached-source fast mode is active: attention-map captures are
    # ~3 GB and not persisted, and flipping a repeat invocation onto the
    # live-source path would silently change its output (drifting source,
    # different controller base maps) — identical commands must produce
    # identical results. The trajectory is still SAVED by cached-mode runs
    # so a later full-mode run of the same clip skips its inversion.
    from videop2p_tpu.serve.store import (
        load_persisted_inversion,
        save_persisted_inversion,
    )
    from videop2p_tpu.utils.inv_cache import (
        content_fingerprint,
        inversion_cache_key,
    )

    # the disk layer's root: a shared --inv_store amortizes one inversion
    # across sweeps / output dirs (keys are content-addressed, so sharing
    # is always safe); default keeps the per-results-dir layout
    store_root = inv_store or output_folder

    inv_key = inversion_cache_key(
        image_path=os.path.abspath(image_path), prompt=prompt,
        steps=NUM_DDIM_STEPS, width=width, video_len=video_len,
        dependent_p2p=dependent_p2p, dependent_weights=dep_w,
        decay_rate=decay_rate, window_size=window_size, ar_sample=ar_sample,
        ar_coeff=ar_coeff, seed=seed,
        # content fingerprints, not path identity: re-tuning the checkpoint
        # in place or replacing the clip's frames must miss, not reuse
        checkpoint=content_fingerprint(pretrained_model_path),
        clip=content_fingerprint(image_path),
        tiny=tiny, guidance=GUIDANCE_SCALE,
        # the VAE-encode dtype changes the latents the trajectory starts from
        mixed_precision=mixed_precision,
    )
    # persistence is single-host/unsharded only: a sharded global trajectory
    # cannot be np.asarray'd from one process, and concurrent writers from a
    # multi-host mesh would race on the same entry
    reuse_inversion = reuse_inversion and mesh is None and jax.process_count() == 1


    if use_cached:
        from videop2p_tpu.pipelines.cached import capture_windows

        # outside these windows the gates multiply the base maps out
        # exactly, so nothing else needs capturing
        cross_len, self_window = capture_windows(ctx, NUM_DDIM_STEPS)

        from videop2p_tpu.pipelines.fast import capture_shapes, choose_cached_maps

        budget_gb = float(os.environ.get("VIDEOP2P_CACHED_MAPS_BUDGET_GB", "6"))

        # the shape check shares cached_fast_edit's OWN capture call, so the
        # budget always sizes exactly what the fused program will materialize
        def shapes_for(tm_dtype):
            return capture_shapes(
                unet_fn, params, sched, latents, cond_src, ctx,
                num_inference_steps=NUM_DDIM_STEPS,
                cross_len=cross_len, self_window=self_window,
                dependent_weight=dep_w,
                dependent_sampler=sampler if dep_w > 0 else None,
                temporal_maps_dtype=tm_dtype,
            )[1]

        # the budget is per chip: on a frame-sharded mesh the capture trees
        # shard over frames/spatial positions, so each chip holds 1/sp of
        # the global bytes — exactly what makes long-video cached mode fit;
        # when bf16 maps overflow, the decision escalates to float8 storage
        # for the (quadratic-in-frames) temporal tree before giving up
        sp_shard = int(mesh.split(",")[1]) if mesh else 1
        fits, tm_dtype, map_gb, per_chip_gb = choose_cached_maps(
            shapes_for, sp=sp_shard, budget_gb=budget_gb
        )
        if not fits:
            print(
                f"[p2p] cached-source maps need {per_chip_gb:.1f} GiB/chip "
                f"even with 1-byte temporal maps (> budget {budget_gb:.1f} "
                "GiB) — falling back to the live source stream"
            )
            use_cached = False
            if reuse_schedule != "off":
                print("[p2p] reuse_schedule disabled with it — the deep-"
                      "feature cache rides the cached edit scan")
                reuse_schedule = "off"
        else:
            print(
                f"[p2p] cached-source fast mode: cross window {cross_len} steps, "
                f"self window {self_window}, maps {map_gb:.2f} GiB global / "
                f"{per_chip_gb:.2f} GiB per chip"
                + (f", temporal maps stored {jnp.dtype(tm_dtype).name}"
                   if tm_dtype is not None else "")
            )

    # consult the persisted products only once the cached-source decision is
    # FINAL (incl. the maps-budget fallback): a budget-forced live run is
    # live on every invocation, so reuse keeps its output-identity guarantee
    # the persisted null embeddings are precision- AND mode-variant
    # products: a mixed/amortized run must never silently reuse fp32 or
    # optimized embeddings (or vice versa)
    null_tag = f"_i{num_inner_steps}" + (
        "_mixed" if null_text_precision == "mixed" else ""
    ) + ("" if null_text_mode == "optimize" else f"_{null_text_mode}")
    reused = (
        load_persisted_inversion(
            store_root, inv_key, want_null=not fast,
            null_tag=null_tag,
        )
        if reuse_inversion and not use_cached
        else None
    )

    key, ik = jax.random.split(key)
    null_embeddings = None
    out = None
    videos = None
    src_err = None  # cached branch only: max |replayed source − encoded clip|
    # {"inversion": rec, "edit": rec} when --attn_maps captured anything
    attn_records = {}
    if use_cached:
        # capture + controlled denoise as ONE device program (the shared
        # pipelines.cached_fast_edit): one dispatch instead of two, and the
        # capture trees never surface as program outputs
        from videop2p_tpu.pipelines import cached_fast_edit

        print("Start Video-P2P!")
        t0 = time.perf_counter()
        with phase_timer("cached_invert_edit"), \
                maybe_trace("cached_invert_edit"):
            # capture-inversion + controlled edit + VAE decode, one program:
            # the chunked decode alone is 4 host dispatches when run eagerly;
            # telemetry rides the SAME program's
            # scan outputs (scalars per step — bytes of extra output)
            def fused_to_video(p, vp, x, k):
                res = cached_fast_edit(
                    unet_fn, p, sched, x, cond_src, cond_all, uncond, ctx,
                    num_inference_steps=NUM_DDIM_STEPS,
                    guidance_scale=GUIDANCE_SCALE,
                    cross_len=cross_len, self_window=self_window,
                    dependent_weight=dep_w,
                    dependent_sampler=sampler if dep_w > 0 else None,
                    key=k,
                    temporal_maps_dtype=tm_dtype,
                    telemetry=telemetry,
                    device_probe=device_probe,
                    attn_maps=attn_maps,
                    reuse_schedule=reuse_schedule,
                )
                traj, edited = res[0], res[1]
                vids = decode_video(bundle.vae, vp, edited.astype(dtype), sequential=True)
                # stream 0 must be the exact inversion reconstruction: 0.0
                # exactly when the cached replay is intact (the serving
                # engine's serve_edit program makes the same comparison)
                src_err = jnp.max(jnp.abs(edited[:1] - x)).astype(jnp.float32)
                return (traj, (vids.astype(jnp.float32) + 1) / 2,
                        src_err, edited) + tuple(res[2:])

            res = instrumented_jit(fused_to_video, program="cached_invert_edit")(
                params, bundle.vae_params, latents, ik
            )
            traj, videos = res[0], res[1]
            src_err = float(np.asarray(jax.device_get(res[2])))
            out = res[3]
            extras = list(res[4:])
            videos = np.asarray(jax.device_get(videos))
            if telemetry:
                tel = extras.pop(0)
                if run_ledger is not None:
                    from videop2p_tpu.obs import (
                        decode_step_stats,
                        summarize_step_stats,
                    )

                    run_ledger.telemetry(
                        "cached_invert_edit",
                        {"summary": summarize_step_stats(tel),
                         "steps": decode_step_stats(tel)},
                    )
            if device_probe is not None:
                _ledger_device_stats(
                    run_ledger, "cached_invert_edit",
                    jax.device_get(extras.pop(0)), device_probe,
                )
            if attn_maps:
                attn_records = jax.device_get(extras.pop(0))
        if run_ledger is not None:
            # measured peak next to the program_analysis predicted peak-HBM
            # (the instrumented_jit cache miss above recorded it) — the
            # ledger summary renders predicted-vs-actual from these two
            run_ledger.memory_snapshot(note="after_cached_edit")
        print(f"[p2p] cached invert+edit+decode done in "
              f"{time.perf_counter() - t0:.1f}s (source replay "
              f"src_err={src_err})")
        if reuse_inversion:
            save_persisted_inversion(
                store_root, inv_key, np.asarray(traj),
                meta={"image_path": image_path, "prompt": prompt,
                      "steps": NUM_DDIM_STEPS, "width": width,
                      "video_len": video_len, "fast": fast},
            )
    elif reused is not None:
        traj_np, null_np = reused
        print(f"[p2p] reusing persisted inversion products (key {inv_key}) — "
              "skipping DDIM inversion"
              + (" and null-text optimization" if null_np is not None else ""))
        traj = jnp.asarray(traj_np)
        x_t = traj[-1]
        if null_np is not None:
            null_embeddings = jnp.asarray(null_np)
    else:
        with phase_timer("ddim_inversion"):
            inv = instrumented_jit(
                lambda p, x, k: ddim_inversion(
                    unet_fn, p, sched, x, cond_src,
                    num_inference_steps=NUM_DDIM_STEPS,
                    dependent_weight=dep_w,
                    dependent_sampler=sampler if dep_w > 0 else None,
                    key=k,
                    attn_maps=attn_maps,
                ),
                program="ddim_inversion",
            )(params, latents, ik)
            if attn_maps:
                traj, inv_attn = inv
                attn_records["inversion"] = jax.device_get(inv_attn)
            else:
                traj = inv
            x_t = jax.block_until_ready(traj[-1])
        if reuse_inversion:
            save_persisted_inversion(
                store_root, inv_key, np.asarray(traj),
                meta={"image_path": image_path, "prompt": prompt,
                      "steps": NUM_DDIM_STEPS, "width": width,
                      "video_len": video_len, "fast": fast},
            )

    if not fast and null_embeddings is None:
        # the official mode exists for reference parity: null-text spends
        # minutes optimizing embeddings so the source stream approximately
        # reconstructs under CFG — the cached --fast mode reconstructs
        # EXACTLY at ~1/20th the cost (pipelines/cached.py)
        print("[p2p] note: --fast (cached-source) reconstructs the source "
              "exactly without null-text optimization")
        # loaded executables count against HBM: drop the inversion program
        # before compiling the null-text grad program, and that one before
        # the CFG edit (a 16 GB chip OOMs with all three resident)
        jax.clear_caches()
        key, nk = jax.random.split(key)
        # mixed precision: the inner loop's forwards/backward run on a
        # bf16-compute clone of the UNet over the SAME params; the fp32
        # islands (scheduler coefficients, Adam state, loss accumulation)
        # are the library's contract (pipelines/inversion.py)
        null_fn = unet_fn
        if null_text_precision == "mixed" and dtype != jnp.bfloat16:
            null_fn = make_unet_fn(bundle.unet.clone(dtype=jnp.bfloat16))
        null_stats = None
        null_kwargs = dict(
            num_inference_steps=NUM_DDIM_STEPS,
            guidance_scale=GUIDANCE_SCALE,
            num_inner_steps=num_inner_steps,
            null_text_precision=null_text_precision,
            null_text_mode=null_text_mode,
            dependent_weight=dep_w,
            dependent_sampler=sampler if dep_w > 0 else None,
            key=nk,
        )
        # phase unit count: inner Adam steps for optimize/hybrid (K=3), one
        # forward per outer step for the closed-form amortized mode
        per_outer = {"optimize": num_inner_steps, "hybrid": 3,
                     "amortized": 1}.get(null_text_mode, num_inner_steps)
        with phase_timer("null_text_optimization",
                         count=NUM_DDIM_STEPS * per_outer,
                         unit="inner-step"), \
             program_label("null_text_fused" if null_text_chunk == 0
                           else "null_text_chunked"):
            # program_label: the fused program jits inside its own cache, so
            # compile events are attributed here rather than per-jit-wrapper
            if null_text_chunk > 0:
                # watchdog fallback: short host-dispatched chunks
                null_embeddings = null_text_optimization(
                    null_fn, params, sched, traj, cond_src, uncond[None],
                    outer_chunk=null_text_chunk, telemetry=telemetry,
                    **null_kwargs,
                )
                if telemetry:
                    null_embeddings, null_tel = null_embeddings
                    null_stats = {"latent_stats": null_tel}
            else:
                # ONE jitted program, trajectory buffer donated (x_t was
                # extracted and the trajectory persisted above — nothing
                # reads it after this point)
                null_embeddings, null_stats = null_text_optimization_fused(
                    null_fn, params, sched, traj, cond_src, uncond[None],
                    donate=True, return_stats=True, telemetry=telemetry,
                    **null_kwargs,
                )
            null_embeddings = jax.block_until_ready(null_embeddings)
        if null_stats is not None and "inner_steps" in null_stats:
            inner_total = int(np.asarray(null_stats["inner_steps"]).sum())
            print(f"[p2p] null-text ({null_text_mode}/{null_text_precision}): "
                  f"{inner_total} inner Adam steps across {NUM_DDIM_STEPS} "
                  f"outer steps, final loss "
                  f"{float(np.asarray(null_stats['final_loss'])[-1]):.3e}")
        if run_ledger is not None and null_stats is not None:
            from videop2p_tpu.obs import decode_null_text_stats, summarize_step_stats

            if "inner_steps" in null_stats:
                run_ledger.telemetry(
                    "null_text_fused", decode_null_text_stats(null_stats)
                )
            elif null_stats.get("latent_stats") is not None:
                run_ledger.telemetry(
                    "null_text_chunked",
                    {"latent": summarize_step_stats(null_stats["latent_stats"])},
                )
            run_ledger.memory_snapshot(note="after_null_text")
        if reuse_inversion:
            # trajectory.npy was written after inversion — only the null
            # embeddings are new here
            save_persisted_inversion(
                store_root, inv_key, None,
                np.asarray(null_embeddings), null_tag=null_tag,
            )
        jax.clear_caches()

    # ---- controlled denoise (skipped when the fused cached path already
    # produced the decoded videos above) ----------------------------------
    if videos is None:
        print("Start Video-P2P!")
        key, ek = jax.random.split(key)
        t0 = time.perf_counter()
        with phase_timer("edit_sample"), maybe_trace("edit_sample"):
            out = instrumented_jit(
                lambda p, x, u, k: edit_sample(
                    unet_fn, p, sched, x, cond_all, u,
                    num_inference_steps=NUM_DDIM_STEPS,
                    guidance_scale=GUIDANCE_SCALE,
                    ctx=ctx,
                    source_uses_cfg=not fast,
                    eta=eta,
                    key=k,
                    dependent_sampler=sampler if (dependent_p2p and eta > 0) else None,
                    null_uncond_embeddings=null_embeddings,
                    telemetry=telemetry,
                    device_probe=device_probe,
                    attn_maps=attn_maps,
                ),
                program="edit_sample",
            )(params, x_t, uncond, ek)
            if telemetry or device_probe is not None or attn_maps:
                out, *edit_extras = out
                if telemetry:
                    edit_tel = edit_extras.pop(0)
                if device_probe is not None:
                    _ledger_device_stats(
                        run_ledger, "edit_sample",
                        jax.device_get(edit_extras.pop(0)), device_probe,
                    )
                if attn_maps:
                    attn_records["edit"] = jax.device_get(edit_extras.pop(0))
            out = jax.block_until_ready(out)
        print(f"[p2p] controlled denoise done in {time.perf_counter() - t0:.1f}s")
        if telemetry and run_ledger is not None:
            from videop2p_tpu.obs import decode_step_stats, summarize_step_stats

            run_ledger.telemetry(
                "edit_sample",
                {"summary": summarize_step_stats(edit_tel),
                 "steps": decode_step_stats(edit_tel)},
            )
        if run_ledger is not None:
            run_ledger.memory_snapshot(note="after_edit")

        # drop the edit executable before compiling the decode program — at
        # fp32 full scale the two do not fit the chip together
        jax.clear_caches()
        with phase_timer("vae_decode"):
            # one jitted dispatch, rescale included — the shared
            # instrumented vae_decode program (serve/programs.py)
            videos = np.asarray(jax.device_get(program_set.decode(out)))

    # stream 0 = inversion reconstruction, stream 1 = edit
    # (run_videop2p.py:688-701; duration 250 ms/frame = 4 fps)
    save_video_gif(videos[0], inversion_gif, fps=4)
    save_video_gif(videos[1], edit_gif, fps=4)
    print(f"[p2p] wrote {inversion_gif} and {edit_gif}")

    # semantic observability (ISSUE 4): attention sidecar + quality
    # metrics + regression verdicts + the self-contained HTML report
    report_path = None
    if run_ledger is not None and (attn_records or quality or report):
        report_path = _semantic_obs(
            run_ledger,
            output_folder=output_folder, save_name=save_name, suffix=suffix,
            prompts=list(prompts), tokenizer=bundle.tokenizer,
            attn_records=attn_records,
            # which prompt stream each capture's heat axis holds: the
            # inversion walk sees only the source; the cached edit batch
            # drops the source stream, the live edit keeps all P
            stream_map={
                "inversion": [0],
                "edit": (list(range(1, len(prompts))) if use_cached
                         else list(range(len(prompts)))),
            },
            quality=quality, report=report,
            source01=np.asarray(jax.device_get((video[0] + 1.0) / 2.0)),
            videos=videos,
        )

    if run_ledger is not None:
        run_ledger.event("artifacts", inversion_gif=inversion_gif,
                         edit_gif=edit_gif, report=report_path)
        run_ledger.memory_snapshot(note="run_end")
        run_ledger.close()
        print(f"[p2p] run ledger: {run_ledger.path}")
    return EditResult(
        inversion_gif, edit_gif,
        branch="cached" if use_cached else "live", src_err=src_err,
        videos=videos, latents=np.asarray(jax.device_get(out), np.float32),
        latent_devices=sorted(d.id for d in out.sharding.device_set),
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default="./configs/videop2p.yaml")
    parser.add_argument("--fast", action="store_true")
    parser.add_argument("--dependent_p2p", default=False, action="store_true")
    parser.add_argument("--tiny", action="store_true",
                        help="random-init tiny models (weightless smoke mode)")
    parser.add_argument("--mesh", type=str, default=None,
                        help="device mesh dp,sp,tp (e.g. 1,4,1: frames over 4 chips)")
    parser.add_argument("--multi", action="store_true",
                        help="per-frame text-embedding mode")
    parser.add_argument("--live_source", action="store_true",
                        help="keep the live source stream in fast mode "
                             "(disable the cached-source replay)")
    parser.add_argument("--no_reuse_inversion", action="store_true",
                        help="do not persist/reuse inversion products "
                             "(trajectory + null embeddings) across runs")
    parser.add_argument("--inv_store", type=str, default=None,
                        help="shared content-addressed root for persisted "
                             "inversion products (serve/store.py disk "
                             "layer) — sweeps amortize one inversion per "
                             "clip across cells; default keeps the "
                             "per-results-dir layout")
    parser.add_argument("--mixed_precision", type=str, default=None,
                        choices=["fp32", "no", "fp16", "bf16"],
                        help="model compute dtype (default fp32 = the "
                             "reference's Stage-2 behavior; bf16 runs the "
                             "MXU at full rate — ~3.5x faster end-to-end)")
    parser.add_argument("--quant_mode", type=str, default="off",
                        choices=["off", "w8", "w8a8"],
                        help="UNet weight quantization at load (--fast "
                             "only): w8 = int8 weights + per-output-channel "
                             "scales stored 1-byte and dequantized inside "
                             "the traced program; w8a8 adds activation "
                             "fake-quant at the attention Dense boundaries")
    parser.add_argument("--reuse_schedule", type=str, default="off",
                        help="cross-step deep-feature reuse in the cached "
                             "fast edit ('uniform:K' or "
                             "'custom:<p0,p1,...>'): listed steps run the "
                             "full UNet, the rest reuse the cached deep "
                             "feature through a shallow path — one compiled "
                             "program either way")
    add_dependent_args(parser)
    add_null_text_args(parser)
    add_obs_args(parser)
    args = parser.parse_args()
    # multi-host: join the process group before any device use (no-op on a
    # single host; see parallel/distributed.py)
    from videop2p_tpu.parallel import initialize_distributed

    initialize_distributed()
    cfg = load_config(args.config)
    # flags win over config for the keys both surfaces expose
    args.multi = args.multi or bool(cfg.pop("multi", False))
    if args.mixed_precision is not None:
        cfg["mixed_precision"] = args.mixed_precision
    if args.null_text_precision is not None:
        cfg["null_text_precision"] = args.null_text_precision
    if args.null_text_chunk is not None:
        cfg["null_text_chunk"] = args.null_text_chunk
    if args.null_text_mode is not None:
        cfg["null_text_mode"] = args.null_text_mode
    args.mesh = args.mesh or cfg.pop("mesh", None)
    main(
        **cfg,
        fast=args.fast,
        dependent=args.dependent,
        dependent_p2p=args.dependent_p2p,
        num_frames=args.num_frames,
        decay_rate=args.decay_rate,
        window_size=args.window_size,
        ar_sample=args.ar_sample,
        ar_coeff=args.ar_coeff,
        eta=args.eta,
        dependent_weights=args.dependent_weights,
        tiny=args.tiny,
        mesh=args.mesh,
        multi=args.multi,
        cached_source=not args.live_source,
        quant_mode=args.quant_mode,
        reuse_schedule=args.reuse_schedule,
        reuse_inversion=not args.no_reuse_inversion,
        inv_store=args.inv_store,
        telemetry=args.telemetry,
        ledger=args.ledger,
        program_analysis=not args.no_program_analysis,
        attn_maps=args.attn_maps,
        quality=args.quality,
        report=args.report,
        device_telemetry=args.device_telemetry,
        latency=args.latency,
        trace_analysis=args.trace_analysis,
        incidents=args.incidents,
    )
