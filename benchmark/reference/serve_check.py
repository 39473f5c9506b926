"""The comparison that decides ``correct`` for a served-edit cell — NOT
COMPLETE, which is why no served cell is listed in ``BENCHMARK.json`` yet
(PERF.md, Open questions, row 0): the 100-call controlled edit chain has no
plain reference, so ``edit_chain_gap`` reads None and every run of a served
cell comes out ``correct: false`` until a later ``benchmark`` PR compares it.

For a sample of the requests the window served, drawn from the seed:
  vae_decode_gap     stream 0 of a served answer is the decode of the
                     store's anchor latents (``src_err == 0`` says the
                     replay is exact); the plain float32 decoder run on the
                     same latents must give the same frames. Relative L2
                     gap over the clip, worst sampled request.
  edit_chain_gap     the edited stream against a plain reference of the
                     cached-replay edit (UNet chain, controller): not
                     compared yet, reads None
  edit_unchanged     1 if an edited stream equals its source stream
  non_finite_frames  frames of the sampled answers holding a NaN or Inf
"""

from __future__ import annotations

TINY_VAE = {"block_out_channels": [8, 16], "layers_per_block": 1,
            "norm_num_groups": 4}


def decode_clip(flat, vae, latents, nx=None):
    """(F, h, w, 4) -> (F, H, W, 3) in [-1, 1], one frame at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.vae import decode_frames

    one = jax.jit(lambda z: decode_frames(flat, vae, z[None], nx=nx)[0])
    return jnp.stack([one(z) for z in latents])


def rel_l2(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2)))


def compare(*, samples, anchor, vae_params, config, limits, rehearse,
            note) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.weights import flatten_named

    out = {"edit_chain_gap": {"value": None,
                              "limit": limits["edit_chain_gap"]}}
    if not samples or anchor is None:
        out["vae_decode_gap"] = {"value": None,
                                 "limit": limits["vae_decode_gap"]}
        return out
    flat = {k: v for k, v in flatten_named(vae_params).items()
            if k.startswith("params/")}
    vae = TINY_VAE if rehearse else config["vae"]
    ref = decode_clip(flat, vae, jnp.asarray(anchor[0]))
    gaps, unchanged, bad = [], 0, 0
    for s in samples:
        v = np.asarray(s["videos"], np.float32)
        bad += int((~np.isfinite(v).all(axis=(2, 3, 4))).sum())
        gaps.append(rel_l2(jnp.asarray(v[0]) * 2.0 - 1.0, ref))
        unchanged += int(np.array_equal(v[0], v[1]))
    note({"phase": "reference", "vae_decode_gaps": gaps,
          "anchor_std": float(np.std(anchor)),
          "ref_abs_max": float(jnp.abs(ref).max())})
    out["vae_decode_gap"] = {"value": max(gaps),
                             "limit": limits["vae_decode_gap"]}
    out["edit_unchanged"] = {"value": unchanged,
                             "limit": limits["edit_unchanged"]}
    out["non_finite_frames"] = {"value": bad,
                                "limit": limits["non_finite_frames"]}
    return out
