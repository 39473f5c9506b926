"""In-program cross-attention observability: fixed-shape per-step records
riding the existing fused DDIM scans.

The reference's primary editing-debug instrument is
``show_cross_attention`` (Prompt-to-Prompt, Hertz et al., 2022): aggregate
the stored cross-attention maps at a low resolution and look at where each
token attends. The UNet here already sows head-averaged probability maps
into the ``attn_store`` collection at every controlled site
(models/attention.py); :func:`attn_step_record` turns one step's store
into a handful of fixed-shape arrays that stack on the scan's ``ys`` —
the same zero-extra-dispatch pattern as :mod:`videop2p_tpu.obs.telemetry`:

  * ``cross_heat`` — (C, rh, rw, L): per conditional stream, the
    head/site/frame-averaged cross-attention heatmap pooled to a fixed
    low resolution (the reference aggregates at 16×16) per token;
  * ``entropy`` — {site: ()} per controlled site, the mean Shannon
    entropy of its attention rows (a collapsing/diffusing site is the
    classic bad-edit signature);
  * ``mask_cov`` / ``mask_heat`` / ``blend_active`` — the LocalBlend mask
    time series: per-stream coverage fraction, the pooled mask itself,
    and whether the blend gate was open at that step (added by the
    sampling loop, which owns the running maps_sum).

Everything is opt-in (``attn_maps=False`` everywhere): the capture-off
programs are the exact pre-capture programs — tests pin the outputs
bit-exact, the cached replay's ``src_err == 0.0`` included. Host-side,
:func:`summarize_attn_record` builds the ledger ``attn_maps`` event and
:func:`save_obs_sidecar` writes the arrays the event references.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ATTN_HEAT_RES",
    "attn_store_leaves",
    "cross_attention_heat",
    "site_entropies",
    "attn_step_record",
    "summarize_attn_record",
    "save_obs_sidecar",
    "load_obs_sidecar",
    "ATTN_SUMMARY_FIELDS",
]

# the reference's aggregation resolution (show_cross_attention res=16)
ATTN_HEAT_RES: Tuple[int, int] = (16, 16)

# keys every summarize_attn_record carries (the ledger `attn_maps` event
# schema tests/test_ledger_schema.py pins); mask keys appear only when the
# record holds a LocalBlend mask series
ATTN_SUMMARY_FIELDS = ("steps", "heat_shape", "sites", "entropy_mean")


def attn_store_leaves(store) -> List[Tuple[str, jax.Array]]:
    """(site_name, head-mean map) pairs from a sown ``attn_store`` tree.

    Accepts either the full mutable-collections dict the UNet apply
    returns (the ``attn_store`` subtree is selected; ``attn_base`` full-
    head capture leaves are excluded) or the subtree itself. Site names
    join the module path (``down_blocks_0/attns_0/.../attn2``); sow's
    tuple wrapping and the ``maps`` leaf name are stripped.
    """
    tree = store
    if isinstance(store, dict):
        if "attn_base" in store or "attn_store" in store:
            tree = store.get("attn_store", {})
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out: List[Tuple[str, jax.Array]] = []
    seen: Dict[str, int] = {}
    for path, leaf in flat:
        names = [
            str(getattr(k, "key")) for k in path
            if isinstance(getattr(k, "key", None), str)
        ]
        name = "/".join(n for n in names if n != "maps")
        n = seen.get(name, 0)
        seen[name] = n + 1
        out.append((f"{name}#{n}" if n else name, leaf))
    return out


def _factor_queries(q: int, latent_hw: Tuple[int, int]) -> Optional[Tuple[int, int]]:
    """Factor a cross site's query count into its (h, w) grid using the
    latent aspect ratio; None when it does not factor (not a spatial
    site)."""
    lh, lw = latent_hw
    if lh <= 0 or lw <= 0:
        return None
    qh = int(round((q * lh / lw) ** 0.5))
    if qh <= 0 or q % qh:
        return None
    return qh, q // qh


def cross_attention_heat(
    store,
    *,
    num_uncond: int,
    num_cond: int,
    video_length: int,
    text_len: int,
    latent_hw: Tuple[int, int],
    heat_res: Tuple[int, int] = ATTN_HEAT_RES,
) -> jax.Array:
    """One step's head/site/frame-averaged per-token cross-attention
    heatmaps, pooled to ``heat_res`` → (num_cond, rh, rw, text_len).

    Sites contribute when their head-mean map is (B, Q, L) with
    ``B = (num_uncond + num_cond)·video_length``, ``L = text_len`` and a
    query grid that factors against the latent aspect ratio — the same
    family of sites the store's Q ≤ 32² guard admits. Uncond streams are
    dropped (only the conditional half is edited); frames average out
    (the per-frame signal lives in the LocalBlend mask series). With no
    qualifying site (e.g. a probe denoiser that sows nothing) the heat
    is zeros — the record shape stays fixed either way.
    """
    B_expect = (num_uncond + num_cond) * video_length
    acc = jnp.zeros((num_cond,) + tuple(heat_res) + (text_len,), jnp.float32)
    n = 0
    for name, leaf in attn_store_leaves(store):
        if not name.split("#")[0].endswith("attn2"):
            continue
        if leaf.ndim != 3 or leaf.shape[-1] != text_len or leaf.shape[0] != B_expect:
            continue
        grid = _factor_queries(leaf.shape[-2], latent_hw)
        if grid is None:
            continue
        maps = leaf.reshape(
            num_uncond + num_cond, video_length, grid[0], grid[1], text_len
        )[num_uncond:].astype(jnp.float32)
        maps = maps.mean(axis=1)  # frames
        maps = jax.image.resize(
            maps, (num_cond,) + tuple(heat_res) + (text_len,), method="linear"
        )
        acc = acc + maps
        n += 1
    if n:
        acc = acc / n
    return acc


def site_entropies(store) -> Dict[str, jax.Array]:
    """Per-site mean Shannon entropy (nats) of the attention rows —
    {site_name: scalar}. Covers every sown head-mean map (cross AND
    temporal sites); site names are trace-time constants, so the dict is
    a fixed-structure scan ``ys`` pytree."""
    out: Dict[str, jax.Array] = {}
    for name, leaf in attn_store_leaves(store):
        if leaf.ndim != 3:
            continue
        p = leaf.astype(jnp.float32)
        ent = -jnp.sum(p * jnp.log(p + 1e-12), axis=-1)
        out[name] = jnp.mean(ent)
    return out


def attn_step_record(
    store,
    *,
    num_uncond: int,
    num_cond: int,
    video_length: int,
    text_len: int,
    latent_hw: Tuple[int, int],
    heat_res: Tuple[int, int] = ATTN_HEAT_RES,
) -> Dict[str, jax.Array]:
    """The per-step capture the pipelines stack on their scan outputs:
    ``cross_heat`` + ``entropy`` (the sampling loop adds the mask series
    where a LocalBlend is configured)."""
    return {
        "cross_heat": cross_attention_heat(
            store,
            num_uncond=num_uncond,
            num_cond=num_cond,
            video_length=video_length,
            text_len=text_len,
            latent_hw=latent_hw,
            heat_res=heat_res,
        ),
        "entropy": site_entropies(store),
    }


# --------------------------------------------------------------- host side --


def summarize_attn_record(rec: Dict) -> Dict:
    """Stacked (num_steps, ...) capture record → the ledger ``attn_maps``
    event payload: step count, heat shape, the site list with mean
    entropies, and the mask-coverage digest when the mask series exists
    (the arrays themselves go to the ``.npz`` sidecar)."""
    heat = np.asarray(rec["cross_heat"])
    entropy = {k: np.asarray(v, np.float64) for k, v in rec.get("entropy", {}).items()}
    out: Dict = {
        "steps": int(heat.shape[0]),
        "heat_shape": list(heat.shape),
        "sites": sorted(entropy),
        "entropy_mean": {
            k: round(float(v.mean()), 4) if v.size else None
            for k, v in sorted(entropy.items())
        },
    }
    if "mask_cov" in rec:
        cov = np.asarray(rec["mask_cov"], np.float64)  # (T, P, F)
        out["mask_cov_final"] = [round(float(v), 4) for v in cov[-1].mean(-1)]
        out["mask_cov_mean"] = round(float(cov.mean()), 4)
    if "blend_active" in rec:
        out["blend_active_steps"] = int(np.asarray(rec["blend_active"]).sum())
    return out


def save_obs_sidecar(path: str, arrays: Dict[str, np.ndarray]) -> str:
    """Write the observability arrays (attention heat stacks, mask series,
    quality curves, reference frames) as one compressed ``.npz`` the
    ledger events point at. numpy-only — readable on any box."""
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    np.savez_compressed(path, **{k: np.asarray(v) for k, v in arrays.items()})
    return path


def load_obs_sidecar(path: str) -> Dict[str, np.ndarray]:
    with np.load(path) as z:
        return {k: z[k] for k in z.files}
