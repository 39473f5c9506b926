"""Scan-fused mixed-precision null-text inversion (pipelines/inversion.py
``null_text_optimization_fused`` + the ``null_text_precision`` knob).

CPU-runnable gates for the official-mode perf work:

  * mixed-vs-fp32 reconstruction parity, pinned as a PSNR band on the same
    CFG replay the official mode reconstructs the source with;
  * the fused single-dispatch program is the host-chunked program
    (identical outputs, fewer dispatches);
  * the fused loop's on-device early stop takes no more inner Adam steps
    than a faithful host-Python-loop-with-break reference;
  * the official-mode e2e record schema (bench.official_e2e_records) is
    exercised off-TPU — keys stable, values null when unmeasured;
  * CachedSource float8 upcast follows the sibling captured maps' dtype
    (ADVICE r5 item 1).

Fake denoisers keep everything eager-CPU-fast (the SURVEY §4 strategy).
"""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from videop2p_tpu.core import DDIMScheduler
from videop2p_tpu.pipelines import (
    ddim_inversion,
    edit_sample,
    null_text_optimization,
    null_text_optimization_fused,
    official_edit,
)

STEPS = 8
SHAPE = (1, 2, 8, 8, 4)  # (B, F, h, w, C)
GUIDANCE = 7.5


@pytest.fixture(scope="module")
def sched():
    return DDIMScheduler.create_sd()


def text_unet():
    """Denoiser whose output depends on the text embedding and latent — a
    real objective for the optimization, computed in the INPUT dtype (so
    the mixed knob's bf16 boundary cast genuinely changes the forward)."""

    def fn(params, sample, t, text, control=None):
        bias = jnp.mean(text, axis=(1, 2))  # (B,)
        return 0.1 * sample + bias[:, None, None, None, None], {}

    return fn


@pytest.fixture(scope="module")
def problem(sched):
    fn = text_unet()
    x0 = jax.random.normal(jax.random.key(0), SHAPE)
    cond = 0.3 * jnp.ones((1, 77, 8))
    uncond = jnp.zeros((1, 77, 8))
    traj = ddim_inversion(fn, None, sched, x0, cond, num_inference_steps=STEPS)
    return fn, x0, cond, uncond, traj


def _recon_psnr(sched, fn, traj, cond, uncond, null_seq, x0):
    """PSNR of the CFG replay driven by the optimized embeddings."""
    out = edit_sample(
        fn, None, sched, traj[-1], cond, uncond[0],
        num_inference_steps=STEPS, guidance_scale=GUIDANCE,
        source_uses_cfg=True, null_uncond_embeddings=null_seq,
    )
    mse = float(jnp.mean((out[0] - x0[0]).astype(jnp.float32) ** 2))
    span = float(jnp.max(x0) - jnp.min(x0))
    return 10 * math.log10(span * span / max(mse, 1e-12))


def test_mixed_precision_recon_within_fp32_psnr_band(sched, problem):
    """The knob's contract: bf16 forwards with fp32 scheduler/Adam/loss
    islands must reconstruct within a few dB of the fp32 path (and both
    must massively beat the unoptimized raw-uncond replay)."""
    fn, x0, cond, uncond, traj = problem
    seqs = {}
    for precision in ("fp32", "mixed"):
        seqs[precision] = null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_precision=precision,
        )
    psnr_fp32 = _recon_psnr(sched, fn, traj, cond, uncond, seqs["fp32"], x0)
    psnr_mixed = _recon_psnr(sched, fn, traj, cond, uncond, seqs["mixed"], x0)
    psnr_raw = _recon_psnr(sched, fn, traj, cond, uncond, None, x0)
    assert psnr_fp32 > psnr_raw + 6.0, (psnr_fp32, psnr_raw)
    assert psnr_mixed > psnr_raw + 6.0, (psnr_mixed, psnr_raw)
    # the parity band: mixed stays within 3 dB of fp32 on the same replay
    assert psnr_mixed > psnr_fp32 - 3.0, (psnr_mixed, psnr_fp32)
    # ... and the mixed path really ran a different (bf16-boundary) forward
    assert not np.allclose(np.asarray(seqs["mixed"]), np.asarray(seqs["fp32"]))


def test_fused_program_matches_host_chunked(sched, problem):
    """One jitted donated-carry dispatch == the host-chunked program, for
    both precision modes (the structural change must not move numbers)."""
    fn, _, cond, uncond, traj = problem
    for precision in ("fp32", "mixed"):
        chunked = null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_precision=precision, outer_chunk=3,
        )
        # donate=False: the module-scope trajectory is reused across tests
        fused, stats = null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_precision=precision, donate=False, return_stats=True,
        )
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(chunked), rtol=2e-5, atol=2e-6
        )
        assert stats["final_loss"].shape == (STEPS,)
        assert stats["inner_steps"].shape == (STEPS,)
        assert stats["inner_steps"].dtype == jnp.int32


def _host_loop_reference(fn, sched, traj, cond, uncond, *, num_inner_steps,
                         epsilon=1e-5):
    """The reference's Python-loop-with-break null-text optimization
    (run_videop2p.py:580-612), eager on host: compute loss → backprop →
    Adam step → break when the pre-update loss cleared the threshold.
    Returns (per-step inner update counts, final embeddings sequence)."""
    adam = optax.adam(1.0)
    timesteps = np.asarray(sched.timesteps(STEPS))
    latent_cur = traj[-1]
    u = uncond.astype(jnp.float32)
    counts, seq = [], []
    for i in range(STEPS):
        t = timesteps[i]
        latent_prev = traj[STEPS - i - 1]
        lr = max(1e-2 * (1.0 - i / 100.0), 0.0)
        thresh = epsilon + i * 2e-5
        eps_cond = fn(None, latent_cur, t, cond, None)[0]

        def loss_fn(u_):
            eps_u = fn(None, latent_cur, t, u_, None)[0]
            eps = eps_u + GUIDANCE * (eps_cond - eps_u)
            prev_rec = sched.prev_step(eps, t, latent_cur, STEPS)
            return jnp.mean((prev_rec - latent_prev) ** 2)

        opt_state = adam.init(u)
        n = 0
        for _ in range(num_inner_steps):
            loss, grads = jax.value_and_grad(loss_fn)(u)
            updates, opt_state = adam.update(grads, opt_state, u)
            u = optax.apply_updates(u, jax.tree.map(lambda g: lr * g, updates))
            n += 1
            if float(loss) < thresh:
                break
        counts.append(n)
        seq.append(u)
        eps_u = fn(None, latent_cur, t, u, None)[0]
        eps = eps_u + GUIDANCE * (eps_cond - eps_u)
        latent_cur = sched.prev_step(eps, t, latent_cur, STEPS)
    return np.asarray(counts), jnp.stack(seq)


def test_fused_early_stop_takes_no_more_steps_than_host_loop(sched, problem):
    """The on-device convergence predicate must stop at least as early as
    the host loop it replaces — a fused loop that silently burns extra
    inner steps would eat the dispatch win it exists for."""
    fn, _, cond, uncond, traj = problem
    # ε chosen so the predicate genuinely fires on this problem: some outer
    # steps converge in a few inner updates, others saturate the bound —
    # a threshold nothing reaches would make the comparison vacuous
    eps = 2.0
    host_counts, host_seq = _host_loop_reference(
        fn, sched, traj, cond, uncond, num_inner_steps=10, epsilon=eps
    )
    _, stats = null_text_optimization_fused(
        fn, None, sched, traj, cond, uncond,
        num_inference_steps=STEPS, guidance_scale=GUIDANCE,
        num_inner_steps=10, epsilon=eps, donate=False, return_stats=True,
    )
    fused_counts = np.asarray(stats["inner_steps"])
    assert (fused_counts <= host_counts).all(), (fused_counts, host_counts)
    assert fused_counts.min() < 10, fused_counts  # early stop fired...
    assert fused_counts.max() == 10, fused_counts  # ...and the bound binds


def test_precision_knob_validation(sched, problem):
    fn, _, cond, uncond, traj = problem
    with pytest.raises(ValueError, match="null_text_precision"):
        null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, null_text_precision="bf16",
        )
    with pytest.raises(ValueError, match="null_text_precision"):
        null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, null_text_precision="fp16",
        )


# ---------------------------------------- amortized / hybrid modes --------


def test_mode_knob_validation_lists_valid_modes(sched, problem):
    """ISSUE 8 satellite: an unknown null_text_mode raises a ValueError
    naming every valid mode (the null_text_precision pattern), on both the
    plain and the fused entry points."""
    fn, _, cond, uncond, traj = problem
    for bad in ("npi", "OPTIMIZE", ""):
        with pytest.raises(ValueError, match="optimize.*amortized.*hybrid"):
            null_text_optimization(
                fn, None, sched, traj, cond, uncond,
                num_inference_steps=STEPS, null_text_mode=bad,
            )
    with pytest.raises(ValueError, match="optimize.*amortized.*hybrid"):
        null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, null_text_mode="closed_form",
        )
    with pytest.raises(ValueError, match="hybrid_inner_steps"):
        null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, null_text_mode="hybrid",
            hybrid_inner_steps=0,
        )


def test_amortized_and_hybrid_recon_parity_band(sched, problem):
    """The tentpole's quality contract: the closed-form amortized mode and
    the joint-refinement hybrid must reconstruct within a few dB of the
    optimize mode on the SAME CFG replay (and massively beat the raw
    uncond), while taking 0 / K inner Adam steps instead of 10×."""
    fn, x0, cond, uncond, traj = problem
    seqs, stats = {}, {}
    for mode in ("optimize", "amortized", "hybrid"):
        seqs[mode], stats[mode] = null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_mode=mode, donate=False, return_stats=True,
        )
    psnr = {m: _recon_psnr(sched, fn, traj, cond, uncond, s, x0)
            for m, s in seqs.items()}
    psnr_raw = _recon_psnr(sched, fn, traj, cond, uncond, None, x0)
    for mode in ("amortized", "hybrid"):
        assert psnr[mode] > psnr_raw + 6.0, (mode, psnr, psnr_raw)
        # the parity band: the cheap modes stay within 3 dB of optimize
        assert psnr[mode] > psnr["optimize"] - 3.0, (mode, psnr)
    # the structural claims: zero inner Adam steps amortized, K=3 hybrid,
    # and the loss record is the same reconstruction objective (finite,
    # comparable across modes)
    assert (np.asarray(stats["amortized"]["inner_steps"]) == 0).all()
    assert (np.asarray(stats["hybrid"]["inner_steps"]) == 3).all()
    for mode in ("amortized", "hybrid"):
        assert np.isfinite(np.asarray(stats[mode]["final_loss"])).all()
    # amortized really is the closed form: uncond := cond at every step
    np.testing.assert_array_equal(
        np.asarray(seqs["amortized"]),
        np.broadcast_to(np.asarray(cond, np.float32),
                        (STEPS,) + cond.shape),
    )


def test_new_modes_fused_matches_chunked(sched, problem):
    """ISSUE 8 satellite: fused == chunked for the NEW modes too — the
    amortized scan chunks like the optimize scan, and the hybrid joint
    refinement is step-independent (absolute-index keys), so slicing the
    step axis must not move numbers."""
    fn, _, cond, uncond, traj = problem
    for mode in ("amortized", "hybrid"):
        chunked = null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_mode=mode, outer_chunk=3,
            return_losses=True, return_inner_steps=True,
        )
        fused, fstats = null_text_optimization_fused(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_mode=mode, donate=False, return_stats=True,
        )
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(chunked[0]), rtol=2e-5, atol=2e-6
        )
        np.testing.assert_allclose(
            np.asarray(fstats["final_loss"]), np.asarray(chunked[1]),
            rtol=2e-5, atol=2e-7,
        )
        np.testing.assert_array_equal(
            np.asarray(fstats["inner_steps"]), np.asarray(chunked[2])
        )


def test_official_edit_mode_knob_matches_split_flow(sched, problem):
    """official_edit(null_text_mode=...) must equal the split flow driven
    by the same mode's embedding sequence — the fused official program and
    the library path cannot drift per mode."""
    fn, _, cond_src, uncond, traj = problem
    cond_all = jnp.concatenate([cond_src, cond_src + 0.2], axis=0)
    for mode in ("amortized", "hybrid"):
        null_seq = null_text_optimization(
            fn, None, sched, traj, cond_src, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_mode=mode,
        )
        split = edit_sample(
            fn, None, sched, traj[-1], cond_all, uncond[0],
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            source_uses_cfg=True, null_uncond_embeddings=null_seq,
        )
        fused, stats = official_edit(
            fn, None, sched, traj, cond_all, uncond[0],
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_mode=mode, donate=False, return_null_stats=True,
        )
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(split), rtol=2e-5, atol=2e-6
        )
        expected_inner = 0 if mode == "amortized" else 3
        assert (np.asarray(stats["inner_steps"]) == expected_inner).all()


def test_cheap_modes_pass_quality_rules_via_obs_diff(
    sched, problem, tmp_path
):
    """The ISSUE 8 acceptance gate, end to end: write the optimize-mode
    reconstruction's quality record as the baseline ledger and each cheap
    mode's (amortized, hybrid) as the new run, then tools/obs_diff.py must
    exit 0 — the substitutes' reconstruction parity clears QUALITY_RULES
    machine-checkably (and a fabricated recon drop exits 1, proving the
    gate has teeth)."""
    import importlib.util

    from videop2p_tpu.obs import RunLedger
    from videop2p_tpu.obs.quality import edit_quality_record

    fn, x0, cond, uncond, traj = problem

    def recon01(null_seq):
        out = edit_sample(
            fn, None, sched, traj[-1], cond, uncond[0],
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            source_uses_cfg=True, null_uncond_embeddings=null_seq,
        )
        lo, hi = float(jnp.min(x0)), float(jnp.max(x0))
        to01 = lambda v: (jnp.clip(v, lo, hi) - lo) / max(hi - lo, 1e-9)  # noqa: E731
        return np.asarray(to01(out[0])), np.asarray(to01(x0[0]))

    ledgers = {}
    for mode in ("optimize", "amortized", "hybrid"):
        null_seq = null_text_optimization(
            fn, None, sched, traj, cond, uncond,
            num_inference_steps=STEPS, guidance_scale=GUIDANCE,
            null_text_mode=mode,
        )
        recon, src = recon01(null_seq)
        summary, _ = edit_quality_record(src, recon, recon)
        path = str(tmp_path / f"{mode}.jsonl")
        with RunLedger(path) as led:
            led.event("quality", program="edit_quality", **summary)
        ledgers[mode] = path

    spec = importlib.util.spec_from_file_location(
        "obs_diff_under_null_test",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools", "obs_diff.py"),
    )
    obs_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_diff)
    for mode in ("amortized", "hybrid"):
        assert obs_diff.main(
            ["obs_diff.py", ledgers["optimize"], ledgers[mode]]
        ) == 0, mode
    # teeth: a fabricated recon drop far below the BASELINE must regress
    # (exit 1) — the rule gates against the optimize run's value
    import json as _json

    dropped = str(tmp_path / "dropped.jsonl")
    with open(ledgers["amortized"]) as f, open(dropped, "w") as g:
        for line in f:
            e = _json.loads(line)
            if e.get("event") == "quality":
                e["recon_psnr"] = float(e["recon_psnr"]) - 40.0
            g.write(_json.dumps(e) + "\n")
    assert obs_diff.main(
        ["obs_diff.py", ledgers["optimize"], dropped]
    ) == 1


def test_official_edit_matches_split_flow(sched, problem):
    """official_edit (null-text + controlled CFG edit as ONE program) must
    equal the split flow that surfaces the embeddings on host."""
    fn, _, cond_src, uncond, traj = problem
    cond_all = jnp.concatenate([cond_src, cond_src + 0.2], axis=0)
    null_seq = null_text_optimization(
        fn, None, sched, traj, cond_src, uncond,
        num_inference_steps=STEPS, guidance_scale=GUIDANCE,
    )
    split = edit_sample(
        fn, None, sched, traj[-1], cond_all, uncond[0],
        num_inference_steps=STEPS, guidance_scale=GUIDANCE,
        source_uses_cfg=True, null_uncond_embeddings=null_seq,
    )
    fused, stats = official_edit(
        fn, None, sched, traj, cond_all, uncond[0],
        num_inference_steps=STEPS, guidance_scale=GUIDANCE,
        donate=False, return_null_stats=True,
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(split), rtol=2e-5, atol=2e-6
    )
    assert stats["inner_steps"].shape == (STEPS,)


def test_inner_step_counts_thread_through_chunked_path(sched, problem):
    """return_inner_steps composes with outer_chunk (the counts concatenate
    across chunks in order)."""
    fn, _, cond, uncond, traj = problem
    full = null_text_optimization(
        fn, None, sched, traj, cond, uncond,
        num_inference_steps=STEPS, return_inner_steps=True,
    )
    chunked = null_text_optimization(
        fn, None, sched, traj, cond, uncond,
        num_inference_steps=STEPS, return_inner_steps=True, outer_chunk=3,
    )
    np.testing.assert_array_equal(np.asarray(full[1]), np.asarray(chunked[1]))
    assert full[1].shape == (STEPS,)


# ------------------------------------------- cached.py float8 upcast --


def test_float8_upcast_follows_sibling_dtype():
    """base_tree_at must upcast float8 temporal maps to the SIBLING captured
    maps' dtype — fp32 cross maps ⇒ fp32 temporal reads (not a hardcoded
    bf16 that silently narrows an fp32 run), bf16 siblings ⇒ bf16, and a
    temporal-only capture falls back to fp32."""
    from videop2p_tpu.pipelines.cached import CachedSource

    f8 = jnp.float8_e4m3fn
    src = jnp.zeros((4, 1, 2, 4, 4, 4))
    temporal = {"block": {"attn_temp": {"probs": jnp.ones((3, 2, 1, 2, 2), f8)}}}

    for sibling_dtype in (jnp.float32, jnp.bfloat16):
        cross = {"block": {"attn2": {"probs": jnp.ones((2, 2, 1, 4, 8), sibling_dtype)}}}
        cached = CachedSource(
            src_latents=src, cross_maps=cross, temporal_maps=temporal,
            cross_len=2, self_window=(0, 3),
        )
        tree = cached.base_tree_at(jnp.asarray(0))
        got = tree["block"]["attn_temp"]["probs"].dtype
        assert got == sibling_dtype, (got, sibling_dtype)
        # the wide sibling itself is untouched
        assert tree["block"]["attn2"]["probs"].dtype == sibling_dtype

    only_temporal = CachedSource(
        src_latents=src, temporal_maps=temporal, self_window=(0, 3),
    )
    tree = only_temporal.base_tree_at(jnp.asarray(1))
    assert tree["block"]["attn_temp"]["probs"].dtype == jnp.float32
