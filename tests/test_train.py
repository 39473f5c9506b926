"""Stage-1 tuning tests: trainable-mask rule, loss descent, freeze guarantee,
lr schedules, checkpoint round-trip."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from videop2p_tpu.core import DDPMScheduler
from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig
from videop2p_tpu.pipelines import make_unet_fn
from videop2p_tpu.train import (
    TrainState,
    TuneConfig,
    count_params,
    latest_checkpoint,
    make_lr_schedule,
    make_optimizer,
    restore_checkpoint,
    save_checkpoint,
    trainable_mask,
    train_step,
    train_steps,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = UNet3DConfig.tiny()
    model = UNet3DConditionModel(config=cfg)
    latents = 0.3 * jax.random.normal(jax.random.key(0), (1, 2, 8, 8, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, cfg.cross_attention_dim))
    variables = jax.jit(model.init)(jax.random.key(2), latents, jnp.asarray(0), text)
    return make_unet_fn(model), dict(variables), latents, text


def test_trainable_mask_rule(tiny):
    """Default rule: attn1.to_q, attn2.to_q and ALL of attn_temp
    (run_tuning.py:50-54,137-141)."""
    _, variables, _, _ = tiny
    params = variables["params"]
    mask = trainable_mask(params)
    flat = jax.tree_util.tree_flatten_with_path(mask)[0]
    on = {jax.tree_util.keystr(p) for p, v in flat if v}
    off = {jax.tree_util.keystr(p) for p, v in flat if not v}
    assert any("attn1" in p and "to_q" in p for p in on)
    assert any("attn2" in p and "to_q" in p for p in on)
    assert any("attn_temp" in p and "to_v" in p for p in on)  # whole module
    assert any("attn_temp" in p and "to_out" in p for p in on)
    assert all("attn1" not in p or "to_q" in p for p in on if "attn_temp" not in p)
    assert any("to_k" in p and "attn_temp" not in p for p in off)
    assert any("conv" in p for p in off)
    n_train = count_params(params, mask)
    n_total = count_params(params)
    assert 0 < n_train < n_total


def test_train_step_descends_and_freezes(tiny):
    fn, variables, latents, text = tiny
    params = variables["params"]
    cfg = TuneConfig(learning_rate=1e-3)
    tx = make_optimizer(cfg)
    mask = trainable_mask(params)
    state = TrainState.create(params, tx)

    step = jax.jit(
        lambda s, k: train_step(
            fn, tx, s, DDPMScheduler.create_sd(), latents, text, k
        )
    )
    key = jax.random.key(0)
    losses = []
    for i in range(8):
        # fixed key: same noise/timestep every step → loss must descend
        state, loss = step(state, key)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert int(state.step) == 8

    # frozen params bit-identical; trainable params changed
    flat0 = jax.tree_util.tree_flatten_with_path(params)[0]
    flat1 = {jax.tree_util.keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(state.params)[0]}
    flatm = {jax.tree_util.keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(mask)[0]}
    changed = unchanged = 0
    for p, v0 in flat0:
        k = jax.tree_util.keystr(p)
        same = np.array_equal(np.asarray(v0), np.asarray(flat1[k]))
        if flatm[k]:
            changed += 0 if same else 1
        else:
            assert same, f"frozen param {k} changed"
            unchanged += 1
    assert changed > 0 and unchanged > 0


@pytest.mark.slow  # ~15 s: compiles the scanned AND the sequential program
def test_train_steps_scan_matches_sequential(tiny):
    """train_steps (one lax.scan over K steps — the CLI's dispatch-batched
    loop) must reproduce K sequential train_step calls with per-step keys
    derived by absolute step index (fold_in(base, step)) — and chunking must
    therefore be boundary-invariant: 4 = 1+3 steps bit-for-bit."""
    fn, variables, latents, text = tiny
    params = variables["params"]
    tx = make_optimizer(TuneConfig(learning_rate=1e-3))
    sched = DDPMScheduler.create_sd()
    K = 4
    base = jax.random.key(7)

    state_seq = TrainState.create(params, tx)
    seq_losses = []
    for i in range(K):
        state_seq, loss = jax.jit(
            lambda s, kk: train_step(fn, tx, s, sched, latents, text, kk)
        )(state_seq, jax.random.fold_in(base, i))
        seq_losses.append(float(loss))

    state_scan = TrainState.create(params, tx)
    state_scan, losses = jax.jit(
        lambda s, kk: train_steps(fn, tx, s, sched, latents, text, kk, num_steps=K)
    )(state_scan, base)
    np.testing.assert_allclose(np.asarray(losses), np.asarray(seq_losses), rtol=1e-5)
    assert int(state_scan.step) == K
    # scanned and unrolled programs fuse differently, and Adam's g/√v
    # normalization amplifies last-ulp gradient differences while v̂ is
    # still near zero — measured divergence is ~1.4e-6 after 4 steps
    # (it was ~3e-7 with the pre-r5 flax GroupNorm's bf16-apply schedule)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6
        ),
        state_scan.trainable, state_seq.trainable,
    )

    # chunk-boundary invariance: 1 then 3 steps == 4 steps
    s2 = TrainState.create(params, tx)
    s2, l1 = train_steps(fn, tx, s2, sched, latents, text, base, num_steps=1)
    s2, l3 = train_steps(fn, tx, s2, sched, latents, text, base, num_steps=3)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(l1), np.asarray(l3)]), np.asarray(losses),
        rtol=1e-5,
    )


def test_dependent_noise_train_path(tiny):
    from videop2p_tpu.core import DependentNoiseSampler

    fn, variables, latents, text = tiny
    params = variables["params"]
    cfg = TuneConfig()
    tx = make_optimizer(cfg)
    state = TrainState.create(params, tx)
    sampler = DependentNoiseSampler.create(num_frames=2, decay_rate=0.5, window_size=2)
    state, loss = jax.jit(
        lambda s, k: train_step(
            fn, tx, s, DDPMScheduler.create_sd(), latents, text, k,
            dependent_sampler=sampler,
        )
    )(state, jax.random.key(0))
    assert np.isfinite(float(loss))


def test_gradient_accumulation_updates_every_k(tiny):
    fn, variables, latents, text = tiny
    params = variables["params"]
    cfg = TuneConfig(gradient_accumulation_steps=2, learning_rate=1e-3)
    tx = make_optimizer(cfg)
    state = TrainState.create(params, tx)
    step = jax.jit(
        lambda s, k: train_step(fn, tx, s, DDPMScheduler.create_sd(), latents, text, k)
    )
    state1, _ = step(state, jax.random.key(0))
    # after 1 micro-step no real update yet
    l0 = jax.tree_util.tree_leaves(params)
    l1 = jax.tree_util.tree_leaves(state1.params)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(l0, l1))
    state2, _ = step(state1, jax.random.key(1))
    l2 = jax.tree_util.tree_leaves(state2.params)
    assert not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(l0, l2))


def test_lr_schedules():
    for name in ["constant", "constant_with_warmup", "linear", "cosine"]:
        cfg = TuneConfig(lr_scheduler=name, lr_warmup_steps=10, max_train_steps=100)
        sched = make_lr_schedule(cfg)
        v0, vw, vend = float(sched(0)), float(sched(10)), float(sched(99))
        assert np.isfinite([v0, vw, vend]).all()
        if name != "constant":
            assert v0 == 0.0 or name == "constant"
        assert vw == pytest.approx(cfg.learning_rate, rel=1e-3)
    with pytest.raises(ValueError):
        make_lr_schedule(TuneConfig(lr_scheduler="nope"))


def test_checkpoint_roundtrip(tmp_path, tiny):
    fn, variables, latents, text = tiny
    params = variables["params"]
    cfg = TuneConfig()
    tx = make_optimizer(cfg)
    state = TrainState.create(params, tx)
    state, _ = jax.jit(
        lambda s, k: train_step(fn, tx, s, DDPMScheduler.create_sd(), latents, text, k)
    )(state, jax.random.key(0))

    out = str(tmp_path / "run")
    save_checkpoint(out, state, 1)
    save_checkpoint(out, state, 5)
    latest = latest_checkpoint(out)
    assert latest is not None and latest.endswith("checkpoint-5")
    restored = restore_checkpoint(latest, state)
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert latest_checkpoint(str(tmp_path / "nope")) is None
    # ISSUE 9 pin: restored leaves are jax-OWNED buffers (copied, not
    # zero-copy views of orbax/tensorstore storage), so the resume path's
    # donated train_steps carry cannot alias memory jax does not own — the
    # use-after-free showed up as garbage weights in the resumed run's
    # next checkpoint before restore_checkpoint copied
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(restored)
               if hasattr(leaf, "shape"))
    donated = jax.jit(lambda t: jax.tree.map(lambda x: x + 0, t),
                      donate_argnums=0)(restored)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(donated)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_preempt_signal_handler_sets_event_and_restores():
    """ISSUE 9 satellite: run_tuning installs SIGTERM/SIGINT handlers that
    set the preemption event (checked at every chunk boundary) and
    restores the previous handlers afterwards."""
    import signal

    from videop2p_tpu.cli import run_tuning as rt

    assert not rt._PREEMPT_EVENT.is_set()
    before = signal.getsignal(signal.SIGTERM)
    restore = rt._install_preempt_handlers()
    try:
        assert signal.getsignal(signal.SIGTERM) is rt._preempt_handler
        assert signal.getsignal(signal.SIGINT) is rt._preempt_handler
        signal.raise_signal(signal.SIGTERM)  # delivered synchronously
        assert rt._PREEMPT_EVENT.is_set()
    finally:
        rt._PREEMPT_EVENT.clear()
        restore()
    assert signal.getsignal(signal.SIGTERM) is before


def _tune_cfg(root, name, **over):
    cfg = dict(
        pretrained_model_path=str(root / f"no_ckpt_{name}"),
        output_dir=str(root / name),
        train_data={"video_path": "data/rabbit", "prompt": "a rabbit is jumping",
                    "n_sample_frames": 2, "width": 16, "height": 16},
        # no validation work: empty prompt list, no inversion
        validation_data={"prompts": [], "use_inv_latent": False},
        max_train_steps=4, steps_per_call=2, log_every=2,
        checkpointing_steps=0, validation_steps=0,
        tiny=True, mixed_precision="no", seed=0,
        gradient_checkpointing=False,
    )
    cfg.update(over)
    return cfg


@pytest.mark.parametrize("backend, impl", [("cpu", "chunked"), ("tpu", "auto")])
def test_tune_main_takes_its_frame_attention_from_the_backend(
    tmp_path, monkeypatch, backend, impl
):
    """``main`` asks ``build_models`` for the frame attention that
    ``ops.attention.training_frame_attention`` chooses from the backend: on
    the CPU "chunked" (today's program — dense at the tiny preset's sites),
    with the backend reported as TPU "auto", the Pallas kernel pair."""
    from videop2p_tpu.cli import run_tuning as rt

    class Stop(Exception):
        pass

    asked = {}

    def build(*args, **kw):
        asked.update(kw)
        raise Stop()

    monkeypatch.setattr(rt, "build_models", build)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(Stop):
        rt.main(**_tune_cfg(tmp_path, "choice"))
    assert asked["frame_attention"] == impl


@pytest.mark.slow  # ~30 s: three tiny end-to-end tuning runs
def test_tuning_preemption_checkpoint_and_bit_identical_resume(
    tmp_path, monkeypatch
):
    """ISSUE 9 satellite — preemption safety e2e: a preempted run saves a
    final checkpoint at the chunk boundary and exits WITHOUT exporting a
    pipeline; auto-resume from `latest` continues to completion and the
    tuned weights are BIT-IDENTICAL to an uninterrupted run (per-step
    noise keys derive from (run key, absolute step), so the resume
    boundary cannot change the noise sequence)."""
    import threading

    from videop2p_tpu.cli import run_tuning as rt

    # deterministic "SIGTERM already pending": the loop preempts at the
    # FIRST chunk boundary (step 2 of 4)
    monkeypatch.setattr(rt, "_PREEMPT_EVENT", threading.Event())
    rt._PREEMPT_EVENT.set()
    out_b = rt.main(**_tune_cfg(tmp_path, "interrupted"))
    ckpt = latest_checkpoint(out_b)
    assert ckpt is not None and ckpt.endswith("checkpoint-2")
    assert not os.path.isfile(os.path.join(out_b, "model_index.json"))

    # auto-resume continues 2 -> 4 and exports the pipeline
    monkeypatch.setattr(rt, "_PREEMPT_EVENT", threading.Event())
    out_b2 = rt.main(**_tune_cfg(tmp_path, "interrupted",
                                 resume_from_checkpoint="latest"))
    assert out_b2 == out_b
    weights_b = os.path.join(out_b, "unet",
                             "diffusion_pytorch_model.safetensors")
    assert os.path.isfile(weights_b)

    # the uninterrupted reference run
    out_a = rt.main(**_tune_cfg(tmp_path, "straight"))
    weights_a = os.path.join(out_a, "unet",
                             "diffusion_pytorch_model.safetensors")
    with open(weights_a, "rb") as fa, open(weights_b, "rb") as fb:
        assert fa.read() == fb.read(), (
            "resumed weights differ from the uninterrupted run — the "
            "resume boundary changed the training trajectory"
        )


@pytest.mark.slow  # ~1 min: three UNet grad compiles, kernels in interpret mode
def test_block_remat_keeps_the_frame_attention_output(monkeypatch):
    """The UNet's remat'd blocks keep the frame-attention kernel's output and
    nothing else: at 32x32 latents the tiny UNet has three sites on the
    kernel pair (interpret mode), and its gradient holds three forward and
    three backward kernels — six forward under a bare ``nn.remat`` — while
    matching the gradient without remat."""
    import collections

    import jax
    import jax.numpy as jnp
    import numpy as np

    from videop2p_tpu.models import UNet3DConditionModel, UNet3DConfig, unet_blocks
    from videop2p_tpu.ops.attention import dense_frame_attention, fused_frame_attention
    from videop2p_tpu.pipelines import make_unet_fn

    def attend(q, k, v):
        if q.shape[3] < 1024:
            return dense_frame_attention(q, k, v)
        return fused_frame_attention(q, k, v, 256, True)

    def kernels(jaxpr):
        counts = collections.Counter(
            e.params["name"] for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        for e in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(e.params):
                counts.update(kernels(sub))
        return dict(counts)

    x = jax.random.normal(jax.random.key(0), (1, 2, 32, 32, 4))
    text = jax.random.normal(jax.random.key(1), (1, 7, 16))
    params = None

    def grad_of(remat):
        nonlocal params
        model = UNet3DConditionModel(
            config=UNet3DConfig.tiny(gradient_checkpointing=remat),
            frame_attention_fn=attend)
        if params is None:
            params = jax.jit(model.init)(jax.random.key(2), x, jnp.asarray(3), text)
        fn = make_unet_fn(model)

        def loss(p):
            out, _ = fn(p, x, jnp.asarray(3), text)
            return jnp.mean(out ** 2)

        grad = jax.grad(loss)
        return kernels(jax.make_jaxpr(grad)(params).jaxpr), jax.jit(grad)(params)

    want_kernels, want = grad_of(False)
    assert want_kernels == {"fused_frame_attention": 3,
                            "fused_frame_attention_bwd": 3}
    got_kernels, got = grad_of(True)
    assert got_kernels == want_kernels
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-7)
    monkeypatch.setattr(unet_blocks, "keep_frame_attention_output", None)
    bare_kernels, _ = grad_of(True)
    assert bare_kernels == {"fused_frame_attention": 6,
                            "fused_frame_attention_bwd": 3}


def _train_step_before_the_split(unet_fn, tx, state, scheduler, latents,
                                 text_embeddings, key):
    """``train_step`` as it stood before ISSUE 28 split it into
    ``loss_step`` over a ``StepLoss`` (git 4c6ffb3, verbatim but for the
    options this test does not use): the diffusion loss hard-wired."""
    import optax

    from videop2p_tpu.train.masking import merge_params

    with jax.named_scope("train.noise"):
        noise_key, t_key = jax.random.split(key)
        noise = jax.random.normal(noise_key, latents.shape, latents.dtype)
        timesteps = jax.random.randint(
            t_key, (latents.shape[0],), 0, scheduler.num_train_timesteps
        )
        noisy = scheduler.add_noise(latents, noise, timesteps)
        target = scheduler.training_target(latents, noise, timesteps)

    def loss_fn(trainable):
        params = merge_params(trainable, state.frozen)
        pred, _ = unet_fn({"params": params}, noisy, timesteps, text_embeddings, None)
        return jnp.mean((pred.astype(jnp.float32) - target.astype(jnp.float32)) ** 2)

    with jax.named_scope("train.loss"):
        loss, grads = jax.value_and_grad(loss_fn)(state.trainable)
    with jax.named_scope("train.optimizer"):
        updates, opt_state = tx.update(grads, state.opt_state, state.trainable)
        trainable = optax.apply_updates(state.trainable, updates)
    return TrainState(step=state.step + 1, trainable=trainable,
                      frozen=state.frozen, opt_state=opt_state), loss


def test_unet_train_step_is_bit_equal_across_the_loss_closure_split(tiny):
    """The UNet's step is the same computation after ``train_step`` became
    ``loss_step(diffusion_loss(...))``: losses and updated leaves bit-equal
    over three steps from the same state and keys."""
    fn, variables, latents, text = tiny
    tx = make_optimizer(TuneConfig(learning_rate=1e-3))
    sched = DDPMScheduler.create_sd()
    new = jax.jit(lambda s, k: train_step(fn, tx, s, sched, latents, text, k))
    old = jax.jit(lambda s, k: _train_step_before_the_split(
        fn, tx, s, sched, latents, text, k))
    s_new = s_old = TrainState.create(variables["params"], tx)
    for i in range(3):
        key = jax.random.fold_in(jax.random.key(5), i)
        s_new, l_new = new(s_new, key)
        s_old, l_old = old(s_old, key)
        assert np.asarray(l_new).tobytes() == np.asarray(l_old).tobytes(), i
    for a, b in zip(jax.tree.leaves(s_new.trainable), jax.tree.leaves(s_old.trainable)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
