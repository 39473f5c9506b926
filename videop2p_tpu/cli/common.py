"""Shared CLI plumbing: config loading, flag surface, model assembly.

Mirrors the reference's OmegaConf-YAML + argparse surface
(/root/reference/run_tuning.py:398-425, run_videop2p.py:703-733) — the
reference's config files run unmodified — including the fork's output-dir
suffix mangling that carries the dependent-noise hyperparameters between
stages (run_tuning.py:97-99, run_videop2p.py:74-78).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import warnings
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from videop2p_tpu.obs.spans import span

__all__ = [
    "load_config",
    "add_dependent_args",
    "add_null_text_args",
    "add_obs_args",
    "dependent_suffix",
    "resolve_pipeline_dir",
    "build_models",
    "build_token_model",
    "check_model_family",
    "MODEL_FAMILIES",
    "TokenModelBundle",
    "encode_prompts",
    "enable_compile_cache",
    "make_run_ledger",
    "setup_mesh",
    "ModelBundle",
]


def make_run_ledger(
    default_path: str,
    *,
    ledger: Optional[str] = None,
    mesh: Optional[str] = None,
    meta: Optional[Dict[str, Any]] = None,
    telemetry: bool = False,
    attn_maps: bool = False,
    quality: bool = False,
    report: bool = False,
    device_telemetry: bool = False,
    latency: bool = False,
    trace_analysis: bool = False,
    program_analysis: bool = True,
    enable: bool = False,
    set_latency_env: bool = True,
    incidents: Optional[str] = None,
):
    """The shared obs-flags → :class:`~videop2p_tpu.obs.RunLedger` wiring.

    Both CLIs, the serving engine and the load generator previously carried
    (or would have carried) near-identical copies of this block: decide
    whether any observability flag implies a ledger, resolve the default
    path, set the process-wide env knobs the pipeline-internal jits check,
    and ACTIVATE the ledger so ``phase_timer`` / the compile listener /
    ``instrumented_jit`` find it. Returns the activated ledger, or None
    when nothing asked for one. ``set_latency_env=False`` keeps ``--latency``
    scoped to this ledger's lifetime (long-lived in-process engines) instead
    of flipping the process-wide env var.
    """
    if not program_analysis:
        os.environ["VIDEOP2P_OBS_NO_ANALYSIS"] = "1"
    if not (enable or telemetry or ledger or attn_maps or quality or report
            or device_telemetry or latency or trace_analysis or incidents):
        return None
    if latency and set_latency_env:
        # pipeline-internal jits (the fused null-text cache) check the
        # env, not the wrapper — set it so their dispatches are timed too
        os.environ["VIDEOP2P_OBS_LATENCY"] = "1"
    from videop2p_tpu.obs import RunLedger

    base_meta = {
        "telemetry": bool(telemetry),
        "attn_maps": bool(attn_maps),
        "quality": bool(quality),
        "device_telemetry": bool(device_telemetry),
        "latency": bool(latency),
        "trace_analysis": bool(trace_analysis),
    }
    base_meta.update(meta or {})
    led = RunLedger(
        ledger or default_path, mesh=mesh, meta=base_meta, latency=latency
    ).activate()
    if incidents:
        # incident plane (ISSUE 18): the flight ring tees this ledger's
        # events, and crash/SIGUSR1 hooks capture bundles for the whole
        # CLI run — the manager rides the process lifetime (one-shot
        # CLIs), so no explicit close is threaded back
        from videop2p_tpu.obs.incident import IncidentManager

        mgr = IncidentManager(str(incidents), crash_hooks=True)
        mgr.attach_ledger(led)
        led.incidents = mgr
    return led


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    The Stage-2 graph alone costs minutes of compile on a cold start; a
    content-addressed on-disk cache makes every later run warm. The
    directory is placed from OUTSIDE the program: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is touched; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored; derived from this package's own
    location, because the path is part of the cache key and a directory
    that moves never hits). Called at the binary boundary (CLI entry
    points, tools, the test suite's conftest) — a library import
    must not mutate global jax config; a second call in one process changes
    nothing. Entries are per backend: a cache filled by CPU runs is of no
    use on the chip."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))
        cache_dir = os.path.join(checkout, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir


def setup_mesh(bundle: "ModelBundle", mesh_spec: str, video_len: int,
               ring_variant: str = None, tp_collectives: str = None):
    """Parse a ``dp,sp,tp`` mesh spec and prepare the bundle for it: build
    the device mesh, wire ring attention into the UNet's uncontrolled
    temporal sites when frames are sharded, and shard the UNet params.
    Returns the mesh. Both CLIs share this; single-clip flows need dp=1.

    ``ring_variant`` picks the ring rotation schedule (``overlap`` — the
    double-buffered default — or ``bidir``/``serial``; None reads
    ``VIDEOP2P_RING_VARIANT``). ``tp_collectives="psum_scatter"`` wires the
    explicit Megatron reduce-scatter output seam on tensor-parallel meshes
    (None reads ``VIDEOP2P_TP_COLLECTIVES``, default ``gspmd`` —
    declarative)."""
    import os as _os

    from videop2p_tpu.parallel import (
        RING_VARIANTS,
        TP_COLLECTIVES,
        make_megatron_out_dot,
        make_mesh,
        make_ring_temporal_fn,
        make_sharded_frame_attention_fn,
        make_sharded_group_norm_fn,
        param_shardings,
    )

    if ring_variant is None:
        from videop2p_tpu.parallel import default_ring_variant

        ring_variant = default_ring_variant()
    if ring_variant not in RING_VARIANTS:
        raise ValueError(
            f"ring_variant must be one of {RING_VARIANTS}, got {ring_variant!r}"
        )
    if tp_collectives is None:
        tp_collectives = _os.environ.get(
            "VIDEOP2P_TP_COLLECTIVES", "gspmd"
        ).strip().lower()
    if tp_collectives not in TP_COLLECTIVES:
        raise ValueError(
            f"tp_collectives must be one of {TP_COLLECTIVES}, "
            f"got {tp_collectives!r}"
        )
    shape = tuple(int(t) for t in str(mesh_spec).split(","))
    if len(shape) != 3:
        raise ValueError(f"--mesh must be dp,sp,tp — got {mesh_spec!r}")
    dp, sp, tp = shape
    if dp != 1:
        raise ValueError(
            "single-clip flows run batch 1 — use dp=1 and put chips on the "
            f"frame/tensor axes, got dp={dp}"
        )
    if video_len % sp:
        raise ValueError(f"sp axis {sp} must divide video_len {video_len}")
    device_mesh = make_mesh(shape)
    print(f"[mesh] data={dp} frames={sp} tensor={tp}")
    if sp > 1 or tp > 1:
        # a model-internal axis is sharded: pjit cannot partition Pallas
        # custom calls, so the fused GroupNorm reaches the mesh through the
        # model's group_norm_fn seam instead of the naked kernel — the same
        # shard_map wrapper pattern as the sharded frame attention below.
        # Sites the wrapper does not cover (frame-pooled resnet slabs whose
        # statistics cross frame shards, slabs over the VMEM gate) fall
        # back to the two-pass XLA math GSPMD partitions as before.
        bundle.unet = bundle.unet.clone(
            group_norm_fn=make_sharded_group_norm_fn(
                device_mesh, impl=bundle.unet.config.group_norm
            )
        )
    if sp > 1:
        # ring attention on the uncontrolled temporal sites (training /
        # inversion; controlled sites stay dense for the P2P edit), and the
        # fused Pallas kernel on the sharded frame-attention sites via
        # shard_map (pjit alone cannot partition a Pallas custom call)
        bundle.unet = bundle.unet.clone(
            temporal_attention_fn=make_ring_temporal_fn(
                device_mesh, variant=ring_variant
            ),
            frame_attention_fn=make_sharded_frame_attention_fn(device_mesh),
        )
    if tp > 1 and tp_collectives == "psum_scatter":
        # explicit Megatron row-parallel outputs: reduce-scatter over the
        # token axis instead of the declarative all-reduce
        bundle.unet = bundle.unet.clone(
            row_parallel_dot=make_megatron_out_dot(device_mesh)
        )
    with span("models.handover", model="unet", mesh=str(mesh_spec)):
        bundle.unet_params = jax.device_put(
            bundle.unet_params,
            param_shardings(device_mesh, bundle.unet_params, tensor_parallel=tp > 1),
        )
    return device_mesh


def load_config(path: str) -> Dict[str, Any]:
    import yaml

    with open(path) as f:
        return yaml.safe_load(f)


def add_dependent_args(parser: argparse.ArgumentParser) -> None:
    """The fork's flag surface (run_tuning.py:401-412, run_videop2p.py:708-720)."""
    parser.add_argument("--dependent", default=False, action="store_true")
    parser.add_argument("--ar_sample", default=False, action="store_true")
    parser.add_argument("--decay_rate", default=0.1, type=float)
    parser.add_argument("--window_size", default=60, type=int)
    parser.add_argument("--ar_coeff", default=0.1, type=float)
    parser.add_argument("--loss_sig", default=False, action="store_true")
    parser.add_argument("--num_frames", default=60, type=int)
    parser.add_argument("--eta", default=0.0, type=float)
    parser.add_argument("--dependent_weights", default=0.0, type=float)


def add_null_text_args(parser: argparse.ArgumentParser) -> None:
    """Official-mode null-text optimization knobs (pipelines/inversion.py)."""
    # defaults are None so a config-file value wins when the flag is unset
    # (the mixed_precision precedence pattern); the effective defaults live
    # on run_videop2p.main (fp32, chunk 0 = fused single dispatch)
    parser.add_argument(
        "--null_text_precision", type=str, default=None,
        choices=["fp32", "mixed"],
        help="null-text inner-loop precision: fp32 (default — reference "
             "behavior) or mixed — bf16 UNet forwards with fp32 "
             "scheduler/Adam/loss islands (~3-4x faster inner steps on "
             "TPU, reconstruction pinned within the fixed-work PSNR band)",
    )
    parser.add_argument(
        "--null_text_chunk", type=int, default=None,
        help="0 (default): run null-text optimization as ONE jitted device "
             "program with the trajectory buffer donated; N>0: split the "
             "outer scan into N-step host-dispatched chunks (the TPU "
             "execution-watchdog fallback for multi-minute fp32 programs)",
    )
    parser.add_argument(
        "--null_text_mode", type=str, default=None,
        choices=["optimize", "amortized", "hybrid"],
        help="how the per-step unconditional embedding is produced: "
             "optimize (default — the reference's per-step inner Adam "
             "loop), amortized (closed-form negative-prompt-inversion "
             "substitute: zero inner Adam steps, one forward per outer "
             "step — ~90%% of the official-mode wall-clock is this inner "
             "loop), or hybrid (amortized seed + <=3 refinement steps "
             "batched jointly across all outer steps). Reconstruction "
             "parity is pinned in tests and gated by the quality rules "
             "(tools/obs_diff.py)",
    )


def add_obs_args(parser: argparse.ArgumentParser) -> None:
    """Observability knobs shared by both CLIs (videop2p_tpu/obs)."""
    parser.add_argument(
        "--telemetry", action="store_true",
        help="thread fixed-shape per-step telemetry (loss curves, "
             "inner-steps-taken, latent abs-max/NaN counts) through the "
             "fused device programs — zero extra dispatches; decoded "
             "host-side into the run ledger",
    )
    parser.add_argument(
        "--ledger", type=str, default=None,
        help="write a JSONL run ledger (phases, XLA compile events, "
             "telemetry summaries, memory snapshots, per-program XLA "
             "cost/memory analyses) to this path; default when --telemetry "
             "is set: <output dir>/run_ledger.jsonl. Render with "
             "tools/ledger_summary.py; diff runs with tools/obs_diff.py",
    )
    parser.add_argument(
        "--no_program_analysis", action="store_true",
        help="skip the automatic compiled-program introspection "
             "(cost/memory analysis + HLO fingerprint per instrumented "
             "program on each compile) — it reads the executable the call "
             "built: printing a UNet-scale module's text takes seconds",
    )
    parser.add_argument(
        "--device_telemetry", action="store_true",
        help="per-device observability on sharded runs (obs/comm.py): "
             "per-device latent abs-max/mean/NaN stats and a cross-replica "
             "divergence scalar riding the fused scans via a shard_map "
             "probe, per-device memory snapshots, and divergence ledger "
             "events gated by the zero-noise-floor COMM_RULES verdict — "
             "requires --mesh; implies a run ledger",
    )
    parser.add_argument(
        "--latency", action="store_true",
        help="per-dispatch execute-latency distributions (obs/timing.py): "
             "every instrumented program accumulates dispatch-return vs "
             "block-until-ready wall-clock into bounded reservoirs, "
             "flushed as execute_timing ledger events (p50/p95/p99/max + "
             "the dispatch-vs-blocked async-overlap split) and gated by "
             "TIMING_RULES; implies a run ledger. Trades async-dispatch "
             "overlap for measured end-to-end latency — values bit-exact "
             "either way",
    )
    parser.add_argument(
        "--trace_analysis", action="store_true",
        help="capture a jax.profiler device trace around the main phase "
             "and mine the raw *.xplane.pb with the stdlib reader "
             "(obs/trace.py — no tensorflow): per-op-family device time, "
             "top ops, compute/collective overlap fraction and idle gaps "
             "as a trace_analysis ledger event + .npz sidecar; implies a "
             "run ledger",
    )
    parser.add_argument(
        "--attn_maps", action="store_true",
        help="capture per-step cross-attention observability riding the "
             "fused DDIM scans (obs/attention.py): pooled per-token "
             "heatmaps, per-site attention entropies, the LocalBlend mask "
             "time series — arrays land in an .npz sidecar referenced by "
             "attn_maps ledger events; capture-off programs stay bit-exact",
    )
    parser.add_argument(
        "--quality", action="store_true",
        help="compute edit-quality metrics after decode (obs/quality.py): "
             "inversion-reconstruction PSNR/SSIM vs the input frames, "
             "background-preservation PSNR outside the blend mask, "
             "adjacent-frame consistency — emitted as a quality ledger "
             "event and gated by the quality RegressionRules",
    )
    parser.add_argument(
        "--report", action="store_true",
        help="render a self-contained HTML edit report (per-word heatmap "
             "grids, mask overlays, null-text loss sparkline, quality "
             "table, regression verdicts) next to the run's outputs — "
             "tools/edit_report.py re-renders it from the ledger+sidecar",
    )
    parser.add_argument(
        "--incidents", type=str, default=None, metavar="DIR",
        help="arm the incident plane (obs/incident.py): an always-on "
             "flight recorder tees the run ledger's most recent events "
             "into a bounded in-memory ring, and anomaly triggers (burn "
             "alert, circuit-breaker open, dispatch deadline, poisoned "
             "stream window, unhandled crash, SIGUSR1 on demand) write "
             "debounced atomic capture bundles under DIR — flight-ring "
             "JSONL, tsdb snapshot, /healthz+/metrics from every target, "
             "manifest with fingerprints and trace-id exemplars. Render "
             "a bundle with tools/incident_report.py",
    )


def dependent_suffix(
    *,
    dependent: bool,
    decay_rate: float,
    window_size: int,
    ar_sample: bool,
    ar_coeff: float,
    eta: float,
    dependent_weights: float,
) -> str:
    """The exact Stage-1↔Stage-2 path contract (run_tuning.py:97-99)."""
    return "_dependent{d}_dr{dr}_ws{ws}_ar{ar}_ac{ac}_eta{e}_dw{dw}".format(
        d=dependent, dr=decay_rate, ws=window_size, ar=ar_sample, ac=ar_coeff,
        e=eta, dw=dependent_weights,
    )


def _is_pipeline_dir(path: str) -> bool:
    return os.path.isdir(os.path.join(path, "unet")) or os.path.isfile(
        os.path.join(path, "model_index.json")
    )


def resolve_pipeline_dir(base_path: str, **suffix_kwargs) -> str:
    """Apply the Stage-1↔Stage-2 suffix contract, tolerating already-resolved
    dirs.

    The reference blindly appends the suffix (run_videop2p.py:74-78), which
    breaks when the caller (e.g. the demo UI's experiment picker) already
    holds the suffixed pipeline dir — the doubled path doesn't exist and
    model loading silently fell back to random init. Preference order:
    suffixed dir if it holds a pipeline, else the given dir if it does, else
    the suffixed dir (downstream loading warns about the missing checkpoint).
    """
    suffixed = base_path + dependent_suffix(**suffix_kwargs)
    if _is_pipeline_dir(suffixed):
        return suffixed
    if _is_pipeline_dir(base_path):
        if suffixed != base_path:
            print(f"[resolve_pipeline_dir] {base_path!r} is already a pipeline "
                  "dir — not appending the dependent suffix")
        return base_path
    return suffixed


@dataclass
class ModelBundle:
    unet: Any
    unet_params: Dict
    vae: Any
    vae_params: Optional[Dict]
    text_encoder: Any
    text_params: Optional[Dict]
    tokenizer: Any
    random_init: bool
    source_dir: Optional[str]
    # the checkpoint's scheduler_config.json (empty for random init) — Stage-2
    # builds its DDIM scheduler from this (run_videop2p.py:101-114)
    scheduler_config: Optional[Dict] = None
    # cached jitted text-encoder apply (a fresh jax.jit wrapper per call would
    # retrace every encode_prompts invocation)
    _text_apply: Any = None

    def make_scheduler(self):
        from videop2p_tpu.core import DDIMScheduler

        if self.scheduler_config:
            return DDIMScheduler.from_config(self.scheduler_config)
        return DDIMScheduler.create_sd()


def build_models(
    pretrained_model_path: Optional[str],
    *,
    dtype: jnp.dtype = jnp.bfloat16,
    frame_attention: str = "auto",
    gradient_checkpointing: bool = False,
    tiny: bool = False,
    seed: int = 0,
) -> ModelBundle:
    """Load a diffusers-layout checkpoint dir, or build random-init models.

    Random init (no checkpoint on disk) keeps every code path drivable in
    weightless environments — outputs are noise, wall-clock is real.
    """
    from videop2p_tpu.models import (
        AutoencoderKL,
        CLIPTextConfig,
        CLIPTextEncoder,
        UNet3DConditionModel,
        UNet3DConfig,
        VAEConfig,
    )
    from videop2p_tpu.utils.tokenizers import load_tokenizer

    key = jax.random.key(seed)
    has_ckpt = pretrained_model_path is not None and os.path.isdir(
        os.path.join(pretrained_model_path, "unet")
    )
    if has_ckpt:
        from videop2p_tpu.models.pipeline_io import load_pipeline

        with span("models.init_or_load", model="pipeline",
                  source=pretrained_model_path):
            loaded = load_pipeline(
                pretrained_model_path,
                dtype=dtype,
                frame_attention=frame_attention,
                gradient_checkpointing=gradient_checkpointing,
            )
        if loaded.inflation_report["kept_init"]:
            print(
                f"[build_models] inflated 2D checkpoint: "
                f"{len(loaded.inflation_report['kept_init'])} temporal params keep init"
            )
        tokenizer = load_tokenizer(pretrained_model_path)
        vae, vae_params = loaded.vae, loaded.vae_params
        text_encoder, text_params = loaded.text_encoder, loaded.text_params
        if vae is None or text_encoder is None:
            # a Stage-1 run that started weightless saves only the UNet — the
            # frozen components have no tuned weights to persist. Backfill
            # with random init so the smoke path stays drivable end-to-end.
            warnings.warn(
                f"checkpoint {pretrained_model_path!r} has no "
                f"{'vae' if vae is None else ''}"
                f"{'/' if vae is None and text_encoder is None else ''}"
                f"{'text_encoder' if text_encoder is None else ''} — "
                "backfilling with RANDOM-INIT components",
                stacklevel=2,
            )
            ucfg = loaded.unet.config
            small = ucfg.block_out_channels[0] < 64  # tiny-shaped checkpoint
            key = jax.random.key(seed)
            if vae is None:
                vcfg = VAEConfig.tiny() if small else VAEConfig()
                vae = AutoencoderKL(config=vcfg, dtype=dtype)
                with span("models.init_or_load", model="vae"):
                    vae_params = dict(jax.jit(vae.init)(
                        key, jnp.zeros((1, 64, 64, vcfg.in_channels), dtype), key
                    ))
            if text_encoder is None:
                ccfg = (
                    CLIPTextConfig.tiny(hidden_size=ucfg.cross_attention_dim)
                    if small else CLIPTextConfig()
                )
                text_encoder = CLIPTextEncoder(config=ccfg, dtype=dtype)
                with span("models.init_or_load", model="text_encoder"):
                    text_params = dict(jax.jit(text_encoder.init)(
                        key, jnp.zeros((1, 8), jnp.int32)
                    ))
        return ModelBundle(
            unet=loaded.unet,
            unet_params=loaded.unet_params,
            vae=vae,
            vae_params=vae_params,
            text_encoder=text_encoder,
            text_params=text_params,
            tokenizer=tokenizer,
            random_init=False,
            source_dir=pretrained_model_path,
            scheduler_config=loaded.scheduler_config,
        )

    warnings.warn(
        f"no checkpoint at {pretrained_model_path!r} — building RANDOM-INIT "
        "models (smoke/benchmark mode; outputs will be noise)",
        stacklevel=2,
    )
    ucfg = UNet3DConfig.tiny() if tiny else UNet3DConfig.sd15()
    ucfg = type(ucfg)(**{
        **ucfg.__dict__,
        "frame_attention": frame_attention,
        "gradient_checkpointing": gradient_checkpointing,
    })
    vcfg = VAEConfig.tiny() if tiny else VAEConfig()
    ccfg = CLIPTextConfig.tiny() if tiny else CLIPTextConfig()
    if tiny:
        ucfg = type(ucfg)(**{**ucfg.__dict__, "cross_attention_dim": ccfg.hidden_size})
    unet = UNet3DConditionModel(config=ucfg, dtype=dtype)
    vae = AutoencoderKL(config=vcfg, dtype=dtype)
    text_encoder = CLIPTextEncoder(config=ccfg, dtype=dtype)
    s = ucfg.sample_size
    probe = jnp.zeros((1, 2, s, s, ucfg.in_channels), dtype)
    tprobe = jnp.zeros((1, 77, ucfg.cross_attention_dim), dtype)
    # one span per model: the init program's trace, compile (or cache load)
    # and dispatch. Its execution is asynchronous: whoever blocks first on
    # the device pays for it (the tuning CLI's VAE encode), no sync is added
    with span("models.init_or_load", model="unet"):
        unet_params = jax.jit(unet.init)(key, probe, jnp.asarray(0), tprobe)
    with span("models.init_or_load", model="vae"):
        vae_params = jax.jit(vae.init)(key, jnp.zeros((1, 64, 64, vcfg.in_channels), dtype), key)
    with span("models.init_or_load", model="text_encoder"):
        text_params = jax.jit(text_encoder.init)(key, jnp.zeros((1, 8), jnp.int32))
    return ModelBundle(
        unet=unet,
        unet_params=dict(unet_params),
        vae=vae,
        vae_params=dict(vae_params),
        text_encoder=text_encoder,
        text_params=dict(text_params),
        tokenizer=load_tokenizer(None),
        random_init=True,
        source_dir=None,
    )


# the tune config's ``model_family``: which model Stage 1 builds and which
# loss it steps on (``tiny`` picks a size inside a family, not a family)
MODEL_FAMILIES = ("unet3d", "deepseek_v32", "granitemoehybrid", "cohere2_moe",
                  "keye_vl2", "falcon_h1")


def check_model_family(name: str) -> str:
    if name not in MODEL_FAMILIES:
        raise ValueError(
            f"unknown model_family {name!r}; known: {list(MODEL_FAMILIES)}"
        )
    return name


@dataclass
class TokenModelBundle:
    """A token model as Stage 1 tunes it: its configuration, its parameter
    tree (the checkpoint's dtype) and ``loss_fn(params, ids) -> (loss, aux)``
    for one document."""

    config: Any
    params: Dict
    loss_fn: Any


def _token_families() -> Dict[str, Any]:
    """``model_family`` → (module, configuration class) of the token models."""
    from videop2p_tpu.models import (cohere2_moe, deepseek, falcon_h1,
                                     granite_hybrid, keye_vl2)

    return {"deepseek_v32": (deepseek, deepseek.DeepSeekV32Config),
            "granitemoehybrid": (granite_hybrid,
                                 granite_hybrid.GraniteHybridConfig),
            "cohere2_moe": (cohere2_moe, cohere2_moe.Cohere2MoeConfig),
            "keye_vl2": (keye_vl2, keye_vl2.KeyeVL2Config),
            "falcon_h1": (falcon_h1, falcon_h1.FalconH1Config)}


def build_token_model(
    model: Optional[Dict[str, Any]],
    *,
    model_family: str,
    dtype: jnp.dtype = jnp.bfloat16,
    gradient_checkpointing: bool = True,
    tiny: bool = False,
    seed: int = 0,
) -> TokenModelBundle:
    """A token family (``models/deepseek.py``, ``models/granite_hybrid.py``,
    ``models/cohere2_moe.py``, ``models/keye_vl2.py``,
    ``models/falcon_h1.py``) from the tune config's
    ``model`` dict — ``config.json`` keys plus the
    chip's share, unknown keys an error — with seeded random weights in the
    checkpoint's dtype (bfloat16): no checkpoint of these families ships,
    and no loader for one is built."""
    module, config_cls = _token_families()[model_family]
    model = dict(model or {})
    choices = bool(model.pop("hand_out_choices", False))
    cfg = config_cls.tiny() if tiny else config_cls.from_dict(model)
    cfg = dataclasses.replace(cfg, remat=bool(gradient_checkpointing),
                              hand_out_choices=choices)
    with span("models.init_or_load", model=model_family):
        params = jax.jit(
            lambda key: module.init_params(key, cfg)
        )(jax.random.key(seed))["params"]
    return TokenModelBundle(
        config=cfg,
        params=params,
        loss_fn=lambda p, ids: module.forward_loss(p, cfg, ids, dtype),
    )


def encode_prompts(bundle: ModelBundle, prompts) -> jax.Array:
    """(P, 77, D) text embeddings via the bundled CLIP encoder."""
    ids = jnp.asarray(
        [bundle.tokenizer.encode_padded(p) for p in prompts], jnp.int32
    )
    if bundle._text_apply is None:
        bundle._text_apply = jax.jit(bundle.text_encoder.apply)
    return bundle._text_apply(bundle.text_params, ids)
