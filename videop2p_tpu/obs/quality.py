"""Edit-quality metrics: is the *edit* good, not just the program fast.

Pure-JAX implementations of the standard reconstruction / preservation
numbers the Video-P2P papers argue about but the repo never recorded:

  * :func:`psnr` / :func:`ssim` — reference-grade image metrics (uniform
    7×7 SSIM window, the skimage default shape) usable inside jit;
  * inversion-reconstruction PSNR — the quantity Null-text Inversion
    (Mokady et al., 2022) exists to maximize: how closely stream 0 of the
    edit output reproduces the input frames;
  * masked background-preservation PSNR — outside the LocalBlend mask the
    edit is supposed to change NOTHING; this measures how true that is;
  * adjacent-frame consistency — the temporal-attention sites exist to
    keep frames coherent; a collapsing edit shows up here first.

:func:`edit_quality_record` folds them into one ledger-ready summary plus
the per-frame curves (arrays go to the ``.npz`` sidecar the ledger event
references). Identical inputs pin the closed forms exactly: PSNR → inf,
SSIM → 1.0 (tests/test_quality.py).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "psnr",
    "ssim",
    "masked_psnr",
    "frame_psnr",
    "adjacent_frame_psnr",
    "edit_quality_record",
    "QUALITY_SUMMARY_FIELDS",
]

# the scalar keys every edit_quality_record summary carries (the ledger
# `quality` event schema tests/test_ledger_schema.py pins); mask-dependent
# keys (background_psnr, mask_coverage) appear only when a mask exists
QUALITY_SUMMARY_FIELDS = (
    "recon_psnr",
    "recon_ssim",
    "edit_adjacent_psnr",
    "source_adjacent_psnr",
)


def psnr(a: jax.Array, b: jax.Array, *, data_range: float = 1.0) -> jax.Array:
    """Peak signal-to-noise ratio in dB over all elements. Identical
    inputs → +inf (MSE 0), by the closed form ``10·log10(R²/MSE)``."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    mse = jnp.mean((a - b) ** 2)
    return 10.0 * (2 * jnp.log10(data_range) - jnp.log10(mse))


def masked_psnr(
    a: jax.Array, b: jax.Array, weight: jax.Array, *, data_range: float = 1.0
) -> jax.Array:
    """PSNR restricted to the region where ``weight`` is nonzero.

    ``weight`` broadcasts against ``a``/``b`` (pass ``1 − mask`` with a
    (F, H, W) or (F, H, W, 1) blend mask to score the BACKGROUND the edit
    was supposed to preserve). An all-zero weight returns NaN rather than
    a fake number — there was nothing to measure.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    w = jnp.broadcast_to(jnp.asarray(weight, jnp.float32), a.shape)
    denom = jnp.sum(w)
    mse = jnp.sum(w * (a - b) ** 2) / jnp.where(denom > 0, denom, jnp.nan)
    return 10.0 * (2 * jnp.log10(data_range) - jnp.log10(mse))


def _uniform_filter(x: jax.Array, win: int) -> jax.Array:
    """Mean filter over the last two axes, VALID padding (the SSIM local
    window)."""
    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add,
        window_dimensions=(1,) * (x.ndim - 2) + (win, win),
        window_strides=(1,) * x.ndim,
        padding="VALID",
    )
    return summed / (win * win)


def ssim(
    a: jax.Array, b: jax.Array, *, data_range: float = 1.0, win_size: int = 7
) -> jax.Array:
    """Mean structural similarity over (..., H, W, C) images.

    Uniform ``win_size``×``win_size`` window (skimage's non-gaussian
    default shape), K1=0.01 / K2=0.03, biased local moments — identical
    inputs give exactly 1.0. Channels are treated as independent images
    (channel axis folds into the batch before filtering).
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    # (..., H, W, C) → (..., C, H, W) so the filter runs over H, W
    a = jnp.moveaxis(a, -1, -3)
    b = jnp.moveaxis(b, -1, -3)
    mu_a = _uniform_filter(a, win_size)
    mu_b = _uniform_filter(b, win_size)
    var_a = _uniform_filter(a * a, win_size) - mu_a * mu_a
    var_b = _uniform_filter(b * b, win_size) - mu_b * mu_b
    cov = _uniform_filter(a * b, win_size) - mu_a * mu_b
    c1 = (0.01 * data_range) ** 2
    c2 = (0.03 * data_range) ** 2
    num = (2 * mu_a * mu_b + c1) * (2 * cov + c2)
    den = (mu_a * mu_a + mu_b * mu_b + c1) * (var_a + var_b + c2)
    return jnp.mean(num / den)


def frame_psnr(a: jax.Array, b: jax.Array, *, data_range: float = 1.0) -> jax.Array:
    """Per-frame PSNR curve for (F, H, W, C) videos → (F,)."""
    return jax.vmap(lambda x, y: psnr(x, y, data_range=data_range))(
        jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    )


def adjacent_frame_psnr(video: jax.Array, *, data_range: float = 1.0) -> jax.Array:
    """Temporal-consistency curve: PSNR between each consecutive frame
    pair of a (F, H, W, C) video → (F−1,). A static clip → all +inf; a
    flickering edit reads as a dip at the offending transition."""
    v = jnp.asarray(video, jnp.float32)
    return frame_psnr(v[1:], v[:-1], data_range=data_range)


def _scalar(x) -> float:
    return float(np.asarray(jax.device_get(x)))


def edit_quality_record(
    source: jax.Array,
    recon: jax.Array,
    edited: jax.Array,
    *,
    mask: Optional[np.ndarray] = None,
    data_range: float = 1.0,
) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:
    """All edit-quality metrics for one run, as ``(summary, curves)``.

    ``source``/``recon``/``edited``: (F, H, W, C) videos in [0, data_range]
    — the input frames, the inversion-reconstruction stream (stream 0 of
    the edit output) and the edited stream. ``mask``: optional (F, H, W)
    float in [0, 1], 1 inside the LocalBlend edit region; background
    metrics score ``1 − mask``. The summary is the ledger ``quality``
    event payload (:data:`QUALITY_SUMMARY_FIELDS` always present); the
    curves are the per-frame arrays for the ``.npz`` sidecar.
    """
    source = jnp.asarray(source, jnp.float32)
    recon = jnp.asarray(recon, jnp.float32)
    edited = jnp.asarray(edited, jnp.float32)
    recon_curve = frame_psnr(recon, source, data_range=data_range)
    edit_adj = adjacent_frame_psnr(edited, data_range=data_range)
    src_adj = adjacent_frame_psnr(source, data_range=data_range)
    summary: Dict[str, float] = {
        "recon_psnr": _scalar(psnr(recon, source, data_range=data_range)),
        "recon_ssim": _scalar(ssim(recon, source, data_range=data_range)),
        "edit_adjacent_psnr": _scalar(jnp.mean(edit_adj)),
        "source_adjacent_psnr": _scalar(jnp.mean(src_adj)),
    }
    curves: Dict[str, np.ndarray] = {
        "recon_psnr_frames": np.asarray(recon_curve),
        "edit_adjacent_psnr_frames": np.asarray(edit_adj),
        "source_adjacent_psnr_frames": np.asarray(src_adj),
    }
    if mask is not None:
        bg = 1.0 - jnp.clip(jnp.asarray(mask, jnp.float32), 0.0, 1.0)
        if bg.ndim == edited.ndim - 1:
            bg = bg[..., None]
        summary["background_psnr"] = _scalar(
            masked_psnr(edited, source, bg, data_range=data_range)
        )
        summary["mask_coverage"] = _scalar(1.0 - jnp.mean(bg))
        curves["background_psnr_frames"] = np.asarray(
            jax.vmap(lambda e, s, w: masked_psnr(e, s, w, data_range=data_range))(
                edited, source, jnp.broadcast_to(bg, edited.shape)
            )
        )
    summary = {
        k: (round(v, 4) if np.isfinite(v) else v) for k, v in summary.items()
    }
    return summary, curves
