#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the repository's main path once, in ONE process, through the entry
points a user calls, at SD-1.5 width with seeded random weights:

  1. ``videop2p_tpu.cli.run_tuning.main`` on ``configs/rabbit-jump-tune.yaml``
     (8 frames, 512², bf16, gradient checkpointing) for a few optimizer
     steps, writing a pipeline directory;
  2. ``videop2p_tpu.cli.run_videop2p.main`` on ``configs/rabbit-jump-p2p.yaml``
     in fast mode against that directory — all 50 DDIM steps, refine +
     reweight + LocalBlend, VAE decode, GIFs;
  3. the serving engine as ``python -m videop2p_tpu.cli.serve`` builds it,
     behind its HTTP server in this process, answering two requests for the
     same clip through ``serve/client.py`` (the second must hit the
     inversion store), then shutting down.

Every phase prints one JSON object; a phase that fails raises, the traceback
goes to stderr and the exit code is non-zero. The LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` and
is printed only after every phase passed on a TPU. The times printed here
are SMOKE times (compile included, one reading each) — not a benchmark.

    python chip_smoke.py              # one chip: tune -> edit -> serve
    python chip_smoke.py --chips 4    # four chips: ONLY the fast edit on
                                      # --mesh 1,4,1 vs the same edit on one
                                      # device (run by hand; count is 4)
    python chip_smoke.py --rehearse   # control-flow rehearsal at tiny size on
                                      # whatever backend there is (CPU here);
                                      # exits 3 and can never print "ok": true

It sets no ``JAX_PLATFORMS``, starts no child process, reads nothing outside
the checkout and writes only under ``outputs/`` and the compile cache.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import shutil
import sys
import threading
import time

T0 = time.perf_counter()
REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "outputs", "chip_smoke")

# depth of the RUN is cut, width never: a few optimizer steps as two calls of
# ONE scanned program (so the second call shows the warm step), and no
# validation — the config's inversion + sampling are two more UNet-scale
# programs, minutes of compile that Stage 2 exercises anyway
TUNE_STEPS = 4
TUNE_STEPS_PER_CALL = 2
# depth of the MODEL is cut too, as the smoke's contract allows: SD-1.5 has
# two layers per UNet block; one keeps every published width (channels
# 320/640/1280/1280, 8 heads, 768-wide text, every attention and norm site's
# shape) and about 0.63 of the layers. The four UNet-scale programs of this
# path each take minutes to compile at full depth — more, together, than the
# 1200 s a cold smoke run is given (PERF.md, PR 21).
UNET_LAYERS_PER_BLOCK = 1
TRAINABLE = ("attn1.to_q", "attn2.to_q", "attn_temp")
# the four-chip edit and the one-device edit are two different bf16 programs
# (ring attention and per-shard kernels against the dense forms): they round
# differently at every layer of 100 UNet calls. Bound on max |Δ| of the
# edited latents, as a share of the one-device latents' largest magnitude:
# 2^-3, i.e. 32 bf16 ulps at the top of the range. The source stream is not
# under this bound — it must replay exactly (src_err == 0.0) on both.
SHARDED_REL_BOUND = 2.0 ** -3


def emit(record: dict) -> None:
    # every record says when it was made and how much host memory the
    # process has ever held (the chip's host gives one process 40 GiB)
    record = dict(record, t_s=round(time.perf_counter() - T0, 1),
                  host_max_rss_gib=round(resource.getrusage(
                      resource.RUSAGE_SELF).ru_maxrss / 2 ** 20, 2))
    print(json.dumps(record, default=str), flush=True)


def cut_depth() -> dict:
    """Have ``build_models``'s random-init branch build SD-1.5 at
    ``UNET_LAYERS_PER_BLOCK``. Steered from this script, not through an
    option of the program: ``UNet3DConfig.sd15`` is what that branch calls.
    The tuned directory Stage 1 writes carries the depth in its
    ``unet/config.json``, so Stage 2 and the server load the same model."""
    from videop2p_tpu.models import UNet3DConfig

    published = UNet3DConfig.sd15
    UNet3DConfig.sd15 = classmethod(lambda cls, **kw: published(
        **{"layers_per_block": UNET_LAYERS_PER_BLOCK, **kw}))
    cfg = UNet3DConfig.sd15()
    return {"block_out_channels": list(cfg.block_out_channels),
            "attention_head_dim": cfg.attention_head_dim,
            "cross_attention_dim": cfg.cross_attention_dim,
            "layers_per_block": cfg.layers_per_block,
            "published_layers_per_block": published().layers_per_block}


def device_record(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def result_line(devices) -> str:
    """The contract's last line. The only place ``"ok": true`` is made, and
    it refuses anything but a TPU."""
    rec = device_record(devices)
    if rec["platform"] != "tpu":
        raise RuntimeError(f"not a TPU: {rec} — no result")
    return json.dumps({"ok": True, "device": rec})


class CacheCounter:
    """Persistent-compile-cache traffic, from jax's own monitoring events:
    ``requests`` (compiles that consulted the cache), ``hits`` (executables
    read back instead of compiled) and ``writes`` (fresh compiles stored)."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self):
        import jax

        self.counts = {"requests": 0, "hits": 0, "writes": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}

    def snapshot(self) -> dict:
        return dict(self.counts)


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where the backend keeps no
    memory statistics, as the CPU does)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def release_device_memory() -> None:
    """Drop what the last phase left on the device: loaded executables count
    against HBM, and the tune step's remat program and the edit program do
    not fit one 16 GB chip together."""
    import ctypes

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
    # and hand the compiler's freed host memory back to the system: the
    # chip's host gives the process 40 GiB, a UNet-scale compile takes ~15
    ctypes.CDLL("libc.so.6").malloc_trim(0)


def ledger_digest(path: str) -> dict:
    """What a run ledger says about the programs a phase ran: compile
    seconds, each program's calls (miss = it compiled; ``blocked_s`` is
    dispatch to ready), its Pallas kernels by name, its predicted peak HBM
    bytes, and — for sharded programs — its collectives."""
    from videop2p_tpu.obs.ledger import read_ledger

    events = read_ledger(path)
    programs: dict = {}
    for e in events:
        if e.get("event") == "program_call":
            p = programs.setdefault(e["program"], {"calls": []})
            p["calls"].append({
                "miss": e.get("cache_miss"),
                "s": e.get("blocked_s", e.get("dispatch_s")),
            })
        elif e.get("event") == "program_analysis":
            p = programs.setdefault(e["program"], {"calls": []})
            p["tpu_custom_calls"] = e.get("tpu_custom_calls", {})
            p["peak_hbm_bytes_predicted"] = e.get("peak_hbm_bytes")
            p["hlo_fingerprint"] = e.get("hlo_fingerprint")
        elif e.get("event") == "comm_analysis":
            p = programs.setdefault(e["program"], {"calls": []})
            p["collectives"] = {
                k: v for k, v in e.items()
                if k.endswith("_count") and v
            }
    return {
        "compile_s": round(sum(e["seconds"] for e in events
                               if e.get("event") == "compile"), 3),
        "programs": programs,
        "phases": {e["name"]: e["seconds"] for e in events
                   if e.get("event") == "phase"},
    }


def require_kernels(programs: dict, program: str, names, on_tpu: bool) -> None:
    """On the chip the named Pallas kernels must be IN the compiled program —
    a run that "works" must not be the XLA fallback."""
    if not on_tpu:
        return
    found = programs.get(program, {}).get("tpu_custom_calls") or {}
    missing = [n for n in names if not found.get(n)]
    if missing:
        raise AssertionError(
            f"program {program!r} holds no {missing} kernel "
            f"(tpu_custom_calls: {found}) — it took the XLA branch"
        )


# --------------------------------------------------------------- phases --


def phase_tune(ctx: dict) -> dict:
    """Stage 1 through ``cli.run_tuning.main``: a few optimizer steps at full
    width, then the pipeline directory Stage 2 reads."""
    import jax
    import numpy as np

    from flax import traverse_util
    from safetensors import safe_open

    from videop2p_tpu.cli.common import build_models, load_config
    from videop2p_tpu.cli.run_tuning import main as tune
    from videop2p_tpu.models.convert import unet3d_params_to_torch
    from videop2p_tpu.ops.attention import training_frame_attention
    from videop2p_tpu.train.masking import trainable_mask

    cfg = load_config(os.path.join(REPO, "configs", "rabbit-jump-tune.yaml"))
    cfg["output_dir"] = os.path.join(OUT, "rabbit-jump")
    cfg["train_data"]["video_path"] = os.path.join(REPO, "data", "rabbit")
    cfg["validation_data"].update(prompts=[], use_inv_latent=False)
    cfg.update(
        max_train_steps=TUNE_STEPS, steps_per_call=TUNE_STEPS_PER_CALL,
        log_every=TUNE_STEPS_PER_CALL, validation_steps=TUNE_STEPS,
        checkpointing_steps=0,
    )
    assert tuple(cfg["trainable_modules"]) == TRAINABLE, cfg["trainable_modules"]
    if ctx["rehearse"]:
        cfg["train_data"].update(n_sample_frames=2, width=16, height=16)
    ledger = os.path.join(OUT, "tune_ledger.jsonl")
    cache0 = ctx["cache"].snapshot()
    t0 = time.perf_counter()
    # latency=True blocks on every train_steps call, so the ledger holds the
    # first (compiling) and the second (warm) call's dispatch-to-ready time
    pipeline_dir = tune(**cfg, tiny=ctx["rehearse"], ledger=ledger,
                        latency=True)
    wall = time.perf_counter() - t0
    digest = ledger_digest(ledger)
    peaks = peak_bytes(ctx["devices"])
    release_device_memory()

    with open(os.path.join(pipeline_dir, "metrics.jsonl")) as f:
        losses = [json.loads(line)["train_loss"] for line in f]
    assert len(losses) == TUNE_STEPS, losses
    assert all(np.isfinite(losses)), f"non-finite train loss: {losses}"

    # the trainable mask, observed: against the same-seed init, exactly the
    # attn1.to_q / attn2.to_q / attn_temp leaves moved and nothing else did.
    # Compared by an exact, order-free checksum of each tensor's bits (the
    # sum of its words mod 2^32 — a transposed layout holds the same words):
    # the init's is taken on the device, the written checkpoint's on the
    # host one tensor at a time, so no copy of the weights crosses over and
    # none sits in host memory next to the compiler's.
    init = build_models(
        None, dtype=jax.numpy.bfloat16,
        gradient_checkpointing=True, tiny=ctx["rehearse"], seed=cfg["seed"],
    ).unet_params["params"]
    sums = jax.device_get(jax.jit(lambda tree: jax.tree.map(
        lambda a: jax.lax.bitcast_convert_type(
            a.astype(np.float32), np.uint32).sum(dtype=np.uint32), tree
    ))(init))
    sums = traverse_util.flatten_dict(sums)
    expect = traverse_util.flatten_dict(trainable_mask(init, TRAINABLE))
    wrong = []
    weights = os.path.join(pipeline_dir, "unet",
                           "diffusion_pytorch_model.safetensors")
    with safe_open(weights, "np") as written:
        for path, leaf in traverse_util.flatten_dict(init).items():
            # the name map wants a tensor to lay out: a zero-stride stand-in
            (key, _), = unet3d_params_to_torch(traverse_util.unflatten_dict(
                {path: np.broadcast_to(np.float32(0), leaf.shape)})).items()
            word_sum = written.get_tensor(key).astype(np.float32).view(
                np.uint32).sum(dtype=np.uint32)
            if bool(word_sum != sums[path]) != expect[path]:
                wrong.append("/".join(path))
    assert not wrong, f"trainable mask violated at {wrong[:8]}"
    del init
    release_device_memory()

    calls = digest["programs"].get("train_steps", {}).get("calls", [])
    assert len(calls) == TUNE_STEPS // TUNE_STEPS_PER_CALL, calls
    ctx["pipeline_dir"] = pipeline_dir
    return {
        "phase": "tune", "wall_s": round(wall, 3),
        "compile_s": digest["compile_s"],
        "first_call_s": calls[0]["s"], "second_call_s": calls[1]["s"],
        "steps": TUNE_STEPS, "losses": losses,
        "trainable_leaves_moved": sum(expect.values()),
        "frozen_leaves_moved": 0,
        # what run_tuning.main asked build_models for; the kernels that are
        # in the compiled step are under programs.train_steps
        "attention": training_frame_attention(),
        "programs": digest["programs"], "phases": digest["phases"],
        "cache": ctx["cache"].since(cache0),
        "peak_bytes_in_use": peaks,
        "pipeline_dir": os.path.relpath(pipeline_dir, REPO),
    }


def run_fast_edit(ctx: dict, *, pretrained: str, ledger: str,
                  mesh: str = None):
    """One ``cli.run_videop2p.main`` call on the rabbit config in fast mode,
    bf16. Returns ``(EditResult, wall seconds, ledger digest, cache Δ)``."""
    import numpy as np

    from videop2p_tpu.cli.common import load_config
    from videop2p_tpu.cli.run_videop2p import main as p2p

    cfg = load_config(os.path.join(REPO, "configs", "rabbit-jump-p2p.yaml"))
    cfg["pretrained_model_path"] = pretrained
    cfg["image_path"] = os.path.join(REPO, "data", "rabbit")
    if ctx["rehearse"]:
        cfg["video_len"] = ctx.get("rehearse_frames", 2)
    cache0 = ctx["cache"].snapshot()
    t0 = time.perf_counter()
    res = p2p(**cfg, fast=True, mixed_precision="bf16", mesh=mesh,
              tiny=ctx["rehearse"], ledger=ledger, latency=True)
    wall = time.perf_counter() - t0
    assert res.branch == "cached", (
        f"the edit took the {res.branch!r} branch, not the cached-source path"
    )
    assert res.src_err == 0.0, f"source replay not exact: {res.src_err}"
    assert np.isfinite(res.videos).all() and np.isfinite(res.latents).all()
    assert res.videos.shape[0] == 2 and res.videos.shape[1] == cfg.get(
        "video_len", 8), res.videos.shape
    assert float(np.abs(res.videos[1] - res.videos[0]).max()) > 0.0, (
        "the edited frames equal the source frames"
    )
    for gif in res:
        assert os.path.getsize(gif) > 0, gif
    return res, wall, ledger_digest(ledger), ctx["cache"].since(cache0)


def phase_edit(ctx: dict) -> dict:
    """Stage 2 through ``cli.run_videop2p.main``: the 50-step cached-source
    fast edit against the directory Stage 1 just wrote. Called once — a
    second call costs two more minutes of a cold run's twenty (my chip run 2,
    PR 21: 112 s, all 24 cache requests hits, no write, identical latents).
    What one call still shows of the compile cache: the run's own analysis
    pass asks for the edit program a second time and must get it back from
    the cache, not from the compiler."""
    import numpy as np

    base = ctx["pipeline_dir"].rsplit("_dependent", 1)[0]
    res, wall, digest, cache = run_fast_edit(
        ctx, pretrained=base, ledger=os.path.join(OUT, "edit_ledger.jsonl"),
    )
    require_kernels(digest["programs"], "cached_invert_edit",
                    ("fused_frame_attention", "fused_group_norm"),
                    ctx["on_tpu"])
    assert cache["hits"] > 0, f"nothing came back from the compile cache: {cache}"
    return {
        "phase": "edit", "steps": 50, "wall_s": round(wall, 3),
        "compile_s": digest["compile_s"],
        "program_s": digest["programs"]["cached_invert_edit"]["calls"][0]["s"],
        "branch": res.branch, "src_err": res.src_err,
        "videos": list(res.videos.shape), "latents": list(res.latents.shape),
        "edit_vs_source_max_abs": float(
            np.abs(res.videos[1] - res.videos[0]).max()),
        "programs": digest["programs"], "phases": digest["phases"],
        "cache": cache, "peak_bytes_in_use": peak_bytes(ctx["devices"]),
        "gifs": [os.path.relpath(g, REPO) for g in res],
    }


def phase_serve(ctx: dict) -> dict:
    """The engine as ``python -m videop2p_tpu.cli.serve`` builds it, at the
    edit phase's precision and geometry, behind its HTTP server in this
    process: two requests for the same clip through ``serve/client.py``."""
    from videop2p_tpu.cli import serve as serve_cli
    from videop2p_tpu.serve.client import EngineClient
    from videop2p_tpu.serve.http import make_server

    out_dir = os.path.join(OUT, "serve")
    argv = ["--checkpoint", ctx["pipeline_dir"], "--mixed_precision", "bf16",
            "--out_dir", out_dir, "--port", "0",
            # one request at a time, and no warm-up on a stand-in controller:
            # the first request compiles for the real one, the second is warm
            "--max_batch", "1", "--no_warm"]
    if ctx["rehearse"]:
        argv += ["--tiny", "--video_len", "2", "--steps", "4"]
    args = serve_cli.build_parser().parse_args(argv)
    cache0 = ctx["cache"].snapshot()
    t0 = time.perf_counter()
    engine = serve_cli.build_engine(args)
    server = make_server(engine, host=args.host, port=args.port).start()
    request = {
        "image_path": os.path.join(REPO, "data", "rabbit"),
        "prompt": "a rabbit is jumping on the grass",
        "prompts": ["a rabbit is jumping on the grass",
                    "a origami rabbit is jumping on the grass"],
        "blend_word": ["rabbit", "rabbit"],
        "eq_params": {"words": ["origami"], "values": [2]},
        "save_name": "origami", "is_word_swap": False,
    }
    records = []
    try:
        client = EngineClient(server.url, timeout_s=30.0)
        assert client.healthz().get("ok"), "engine not healthy"
        for _ in range(2):
            t1 = time.perf_counter()
            rec = client.wait(client.submit(request), timeout_s=1000.0)
            rec["client_s"] = round(time.perf_counter() - t1, 3)
            records.append(rec)
    finally:
        server.close()
        engine.close(drain_s=args.drain_s)
    wall = time.perf_counter() - t0
    for rec in records:
        assert rec["status"] == "done", rec
        assert rec["src_err"] == 0.0, rec
        assert os.path.getsize(rec["edit_gif"]) > 0
    first, second = records
    assert not first["store_hit"], first
    assert second["store_hit"], "the second request missed the inversion store"
    assert second["compile_events"] == 0, second
    assert first["content_sha256"] == second["content_sha256"], (
        "the same request gave two different answers"
    )
    digest = ledger_digest(engine.ledger.path)
    require_kernels(digest["programs"], "serve_edit",
                    ("fused_frame_attention", "fused_group_norm"),
                    ctx["on_tpu"])
    require_kernels(digest["programs"], "serve_invert",
                    ("fused_frame_attention", "fused_group_norm"),
                    ctx["on_tpu"])
    keep = ("status", "client_s", "total_s", "dispatch_s", "src_err",
            "store_hit", "store_source", "compile_events")
    return {
        "phase": "serve", "wall_s": round(wall, 3),
        "compile_s": digest["compile_s"],
        "requests": [{k: r.get(k) for k in keep} for r in records],
        "programs": digest["programs"],
        "cache": ctx["cache"].since(cache0),
        "peak_bytes_in_use": peak_bytes(ctx["devices"]),
        "shutdown": "clean",
    }


def phase_edit_four_chips(ctx: dict) -> dict:
    """Only with ``--chips 4``: the fast edit on ``--mesh 1,4,1`` (frames
    over four chips — ring attention at the temporal sites, the
    shard_map-wrapped kernels at the frame sites) against the same edit,
    same seed, on one of the four devices."""
    import numpy as np

    devices = ctx["devices"]
    ctx["rehearse_frames"] = 4  # a tiny rehearsal still needs a frame a chip
    # no pipeline directory here: both runs take the seeded random-init
    # branch of build_models, so they hold the same weights
    absent = os.path.join(OUT, "no-checkpoint")
    sharded, wall4, digest4, cache4 = run_fast_edit(
        ctx, pretrained=absent, mesh="1,4,1",
        ledger=os.path.join(OUT, "edit4_ledger.jsonl"),
    )
    peaks4 = peak_bytes(devices)
    prog4 = digest4["programs"]["cached_invert_edit"]
    require_kernels(digest4["programs"], "cached_invert_edit",
                    ("fused_frame_attention", "fused_group_norm"),
                    ctx["on_tpu"])
    assert len(sharded.latent_devices) == 4, sharded.latent_devices
    assert prog4.get("collectives"), (
        "the sharded program holds no collective — nothing was partitioned"
    )
    if all(p is not None for p in peaks4):
        assert max(peaks4) < 2 * min(peaks4), (
            f"per-device peaks {peaks4}: one device holds the work of all"
        )
    lat4, devices4, src_err4 = (sharded.latents, sharded.latent_devices,
                                sharded.src_err)
    del sharded
    release_device_memory()

    single, wall1, digest1, cache1 = run_fast_edit(
        ctx, pretrained=absent,
        ledger=os.path.join(OUT, "edit1_ledger.jsonl"),
    )
    assert len(single.latent_devices) == 1, single.latent_devices
    lat1 = single.latents
    scale = float(np.abs(lat1).max())
    delta = float(np.abs(lat4 - lat1).max())
    bound = SHARDED_REL_BOUND * scale
    record = {
        "phase": "edit_four_chips", "mesh": "1,4,1", "steps": 50,
        "sharded": {
            "wall_s": round(wall4, 3), "compile_s": digest4["compile_s"],
            "program_s": prog4["calls"][0]["s"], "src_err": src_err4,
            "latent_devices": devices4,
            "peak_bytes_in_use": peaks4,
            "collectives": prog4.get("collectives"),
            "tpu_custom_calls": prog4.get("tpu_custom_calls"),
            "peak_hbm_bytes_predicted": prog4.get("peak_hbm_bytes_predicted"),
            "cache": cache4,
        },
        "one_device": {
            "wall_s": round(wall1, 3), "compile_s": digest1["compile_s"],
            "program_s":
                digest1["programs"]["cached_invert_edit"]["calls"][0]["s"],
            "src_err": single.src_err,
            "latent_devices": single.latent_devices,
            "peak_bytes_in_use": peak_bytes(devices), "cache": cache1,
        },
        "max_abs_delta": delta, "mean_abs_delta":
            float(np.abs(lat4 - lat1).mean()),
        "latents_max_abs": scale, "bound": bound,
        "bound_rule": f"{SHARDED_REL_BOUND} x max|one-device latents| "
                      "(2^-3: 32 bf16 ulps at the top of the range)",
    }
    emit(record)  # before the verdict: a miss still shows its numbers
    assert delta <= bound, f"sharded vs one device: {delta} > {bound}"
    return None


PHASES_ONE_CHIP = (phase_tune, phase_edit, phase_serve)
PHASES_FOUR_CHIPS = (phase_edit_four_chips,)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: only the sharded fast edit against the "
                         "one-device edit (needs four chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny-size control-flow rehearsal on any backend; "
                         "exits 3, never prints a result")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU — jax.devices()[0] is "
              f"{devices[0].platform!r} ({devices[0].device_kind}). "
              "Nothing was run.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax found {len(devices)}", file=sys.stderr)
        return 2

    from videop2p_tpu.cli.common import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    ctx = {"devices": devices, "on_tpu": on_tpu, "rehearse": args.rehearse,
           "cache": CacheCounter()}
    emit({"phase": "start", "device": device_record(devices),
          "rehearsal": args.rehearse, "chips": args.chips,
          "unet": "tiny" if args.rehearse else cut_depth(),
          "jax": jax.__version__, "compile_cache_dir": cache_dir,
          "compile_cache_entries": entries_before})
    t0 = time.perf_counter()
    for phase in (PHASES_FOUR_CHIPS if args.chips == 4 else PHASES_ONE_CHIP):
        record = phase(ctx)  # a failed phase raises: no record, no result
        if record is not None:
            emit(record)
        release_device_memory()
    emit({"phase": "end", "wall_s": round(time.perf_counter() - t0, 3),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_before": entries_before,
          "compile_cache_entries_after": cache_entries(cache_dir),
          "cache": ctx["cache"].snapshot(),
          "threads_alive": threading.active_count()})
    if args.rehearse:
        emit({"ok": False, "rehearsal": True,
              "device": device_record(devices)})
        return 3
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
