"""v5e-4 projection model for the fast edit: compute + ICI-collective budget.

Round 2 projected the 4-chip wall-clock with a bare 0.8 efficiency constant
whose justification lived in prose. This module derives the projection
mechanically, so it is reproducible from repo contents (VERDICT r2 item 5):

* **Traffic table** — for the (dp=1, sp=4, tp=1) sequence-parallel mesh the
  CLI ships (``--mesh 1,4,1``; frames shard over chips), the per-step ICI
  bytes are enumerated from the UNet's attention-site shapes:
  - *frame-0 KV broadcast*: every frame-attention site needs frame 0's
    keys/values (reference semantics, tuneavideo/models/attention.py:296-302)
    — each non-owner chip ingests the full (B, H, N_s, D) K and V in bf16.
  - *temporal all-gather*: Stage-2 temporal sites are CONTROLLED (P2P edits
    their f×f maps), so each chip gathers the full frame axis for its local
    spatial shard — (B, N_s/sp, F, C_s) K and V in bf16 per site.
* **Compute scaling** — every per-frame op (convs, FF, norms, frame-attn
  queries) divides by sp; the single-chip step time is the measured input.
* **Bandwidth model** — ingress-bound collectives at ``ici_gbps`` effective
  per-chip bandwidth, no compute/communication overlap assumed (both
  conservative). v5e chips have 4 ICI links; public specs put per-chip
  aggregate bandwidth at ~400 GB/s (bidirectional); 100 GB/s effective
  ingress is the deliberately conservative default.

Run ``python tools/projection.py`` to (re)generate ``docs/PROJECTION.md``
with the traffic table and the sensitivity over ICI bandwidths; ``bench.py``
calls :func:`project` with its measured phase times so the recorded
``projected_v5e4_s`` is always derived from this model, not a constant.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# SD-1.5 UNet attention sites at 512² (64×64 latents): (N_spatial, channels,
# heads, head_dim, count) per level — 2 transformer layers per down level,
# 3 per up level, 1 mid (models/unet.py sd15 topology; verified against the
# round-3 xplane trace: five N=4096 frame-attn fusions per forward).
SD15_SITES: List[Tuple[int, int, int, int, int]] = [
    (64 * 64, 320, 8, 40, 5),   # down0 ×2 + up3 ×3
    (32 * 32, 640, 8, 80, 5),   # down1 ×2 + up2 ×3
    (16 * 16, 1280, 8, 160, 5),  # down2 ×2 + up1 ×3
    (8 * 8, 1280, 8, 160, 1),   # mid
]


def traffic_table(batch: int, frames: int, sp: int) -> List[Dict]:
    """Per-step ICI bytes per attention site for the sp-way frame shard."""
    rows = []
    for n_s, ch, heads, d, count in SD15_SITES:
        kv_broadcast = 2 * batch * heads * n_s * d * 2  # K+V, bf16
        # controlled temporal sites: all-gather K+V over the frame axis for
        # the chip's local spatial shard (queries stay local)
        temporal_gather = 2 * batch * (n_s // sp) * frames * ch * 2 * (sp - 1)
        rows.append({
            "site": f"{int(n_s ** 0.5)}x{int(n_s ** 0.5)}",
            "instances": count,
            "kv_broadcast_mb": round(kv_broadcast / 1e6, 2),
            "temporal_gather_mb_per_chip": round(temporal_gather / sp / 1e6, 2),
            "total_mb_per_chip_per_step": round(
                count * (kv_broadcast + temporal_gather / sp) / 1e6, 2
            ),
        })
    return rows


def project(
    inv_s: float,
    edit_s: float,
    *,
    steps: int = 50,
    frames: int = 8,
    sp: int = 4,
    ici_gbps: float = 100.0,
    shard_inv_s: Optional[float] = None,
    shard_edit_s: Optional[float] = None,
    edit_streams: int = 3,
    efficiency: float = 1.0,
) -> Dict:
    """Project the 4-chip fast-edit wall-clock from measured single-chip
    phase times. Returns the projection plus its full evidence.
    ``edit_streams``: 3 for the live fast edit, 2 for the cached-source mode
    (whose capture trees shard over frames with no extra collectives —
    tests/test_parallel.py pins sharded==unsharded for it).

    ``shard_inv_s`` / ``shard_edit_s``: MEASURED single-chip wall-clock of
    the frames/sp-frame working point — exactly the per-chip compute of the
    sharded mesh (minus collectives), capturing the small-batch efficiency
    loss that a bare /sp would hide. bench.py measures these in its extended
    phases; without them the model falls back to linear scaling. (Caveat:
    the F/sp proxy runs temporal attention at (F/sp)² instead of the sharded
    N/sp×F² — a few ms/step either way at F≤8 since temporal sites are tiny.)
    """
    t_inv = traffic_table(1, frames, sp)   # inversion: 1 cond stream
    t_edit = traffic_table(edit_streams, frames, sp)
    inv_mb = sum(r["total_mb_per_chip_per_step"] for r in t_inv)
    edit_mb = sum(r["total_mb_per_chip_per_step"] for r in t_edit)
    coll_inv = inv_mb * 1e6 / (ici_gbps * 1e9) * steps
    coll_edit = edit_mb * 1e6 / (ici_gbps * 1e9) * steps
    # "is not None": a legitimate 0.0 shard reading must not silently fall
    # back to linear scaling
    use_shard = shard_inv_s is not None and shard_edit_s is not None
    proj_inv = (shard_inv_s if use_shard else inv_s / sp / efficiency) + coll_inv
    proj_edit = (shard_edit_s if use_shard else edit_s / sp / efficiency) + coll_edit
    total = proj_inv + proj_edit

    # Uncertainty band (VERDICT r4 item 6: the point estimate moved 20 % in
    # one round when the compute model switched from linear-in-sp to the
    # measured shard proxy — so the record carries BOTH models at both
    # bandwidth extremes, not three significant figures of one of them).
    #   optimistic  = linear compute scaling (ignores small-batch loss; the
    #                 r3 model) at 2× the default effective ICI bandwidth;
    #   pessimistic = the measured F/sp shard proxy (includes small-batch
    #                 loss AND host timing noise — the
    #                 proxy phases are 2-4 s where ±0.3 s is ~15 %) at half
    #                 the default bandwidth.
    # The true 4-chip number should land inside; quote the range.
    candidates = []
    for bw in (ici_gbps / 2, ici_gbps, ici_gbps * 2):
        ci = inv_mb * 1e6 / (bw * 1e9) * steps
        ce = edit_mb * 1e6 / (bw * 1e9) * steps
        candidates.append(inv_s / sp + ci + edit_s / sp + ce)  # linear, ideal
        if efficiency < 1.0:
            # derated linear — the compute model configs without their own
            # shard proxy actually use; without this the point estimate
            # could sit outside its own range
            candidates.append(
                inv_s / sp / efficiency + ci + edit_s / sp / efficiency + ce
            )
        if use_shard:
            candidates.append(shard_inv_s + ci + shard_edit_s + ce)
    lo, hi = min(candidates), max(candidates)

    return {
        "projected_v5e4_s": round(total, 2),
        "projected_v5e4_range_s": [round(lo, 1), round(hi, 1)],
        "parallel_efficiency": round((inv_s + edit_s) / (sp * total), 3),
        "assumptions": {
            "sp": sp,
            "ici_effective_gbps": ici_gbps,
            "overlap": "none (conservative)",
            "compute_scaling": (
                "measured: single-chip F/sp-frame phases stand in for the "
                "per-chip shard" if use_shard
                else "linear in sp (per-frame ops shard cleanly; "
                     "tests/test_parallel.py proves sharded==unsharded)"),
        },
        "inversion": {
            "single_chip_s": inv_s,
            "collective_s": round(coll_inv, 3),
            "projected_s": round(proj_inv, 2),
            "traffic_per_step": t_inv,
        },
        "edit": {
            "single_chip_s": edit_s,
            "collective_s": round(coll_edit, 3),
            "projected_s": round(proj_edit, 2),
            "traffic_per_step": t_edit,
        },
    }


def project_official(
    inv_s: float,
    null_s: float,
    off_edit_s: float,
    *,
    steps: int = 50,
    frames: int = 8,
    inner_steps: int = 3,
    sp: int = 4,
    ici_gbps: float = 100.0,
    efficiency: float = 1.0,
) -> Dict:
    """Project the official-mode edit (inversion + null-text + full-CFG
    controlled edit) onto the sp-chip frame-sharded mesh.

    Null-text is per-frame UNet work (forwards + a remat backward on the
    uncond branch) and shards over frames like everything else; its
    per-outer-step collective volume is the 1-stream traffic times the
    forward-equivalent count ``2 + 3·inner`` (backward ≈ 2 forwards of
    traffic — conservative). ``efficiency`` (≤1) derates the per-chip
    compute for small-batch loss, measured via the F/sp shard proxy.
    """
    t1 = traffic_table(1, frames, sp)
    t4 = traffic_table(4, frames, sp)
    mb1 = sum(r["total_mb_per_chip_per_step"] for r in t1)
    mb4 = sum(r["total_mb_per_chip_per_step"] for r in t4)
    coll_inv = mb1 * 1e6 / (ici_gbps * 1e9) * steps
    coll_null = mb1 * 1e6 / (ici_gbps * 1e9) * steps * (2 + 3 * inner_steps)
    coll_off = mb4 * 1e6 / (ici_gbps * 1e9) * steps
    proj = (
        (inv_s / sp / efficiency + coll_inv)
        + (null_s / sp / efficiency + coll_null)
        + (off_edit_s / sp / efficiency + coll_off)
    )
    single = inv_s + null_s + off_edit_s
    return {
        "projected_v5e4_s": round(proj, 2),
        "single_chip_s": round(single, 2),
        "parallel_efficiency": round(single / (sp * proj), 3),
        "phases": {
            "inversion_s": round(inv_s / sp / efficiency + coll_inv, 2),
            "null_text_s": round(null_s / sp / efficiency + coll_null, 2),
            "official_edit_s": round(off_edit_s / sp / efficiency + coll_off, 2),
        },
        "assumptions": {
            "sp": sp, "ici_effective_gbps": ici_gbps,
            "compute_efficiency": round(efficiency, 3),
            "null_traffic_fwd_equivalents_per_outer": 2 + 3 * inner_steps,
            "null_variant": f"fixed {inner_steps} inner steps (stable record)",
        },
    }


def project_long(
    e2e_s: float,
    *,
    steps: int = 50,
    frames: int = 24,
    sp: int = 4,
    ici_gbps: float = 100.0,
    efficiency: float = 1.0,
) -> Dict:
    """Project the 24-frame fast edit (BASELINE config 3) onto sp chips:
    frames/sp = 6 frames per chip; inversion (1 stream) + live fast edit
    (3 streams) collectives at the 24-frame site shapes."""
    mb = sum(
        r["total_mb_per_chip_per_step"]
        for t in (traffic_table(1, frames, sp), traffic_table(3, frames, sp))
        for r in t
    )
    coll = mb * 1e6 / (ici_gbps * 1e9) * steps
    proj = e2e_s / sp / efficiency + coll
    return {
        "projected_v5e4_s": round(proj, 2),
        "single_chip_s": round(e2e_s, 2),
        "parallel_efficiency": round(e2e_s / (sp * proj), 3),
        "collective_s": round(coll, 3),
        "assumptions": {
            "sp": sp, "ici_effective_gbps": ici_gbps,
            "frames_per_chip": frames // sp,
            "compute_efficiency": round(efficiency, 3),
        },
    }


def main() -> None:
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(__doc__.strip())
        return
    # measured single-chip phase times from the committed record; the
    # headline inversion_s/edit_s are the CACHED-mode pair — the projection
    # models the live sharded path, so prefer the live A/B readings
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench_details.json")) as f:
        bd = json.load(f)["breakdown"]
    inv_s = bd.get("inversion_live_s", bd["inversion_s"])
    edit_s = bd.get("edit_live_s", bd["edit_s"])
    shard_kw = {}
    if "shard2_inversion_s" in bd and "shard2_edit_s" in bd:
        shard_kw = dict(shard_inv_s=bd["shard2_inversion_s"],
                        shard_edit_s=bd["shard2_edit_s"])

    lines = [
        "# v5e-4 fast-edit projection (generated by tools/projection.py)",
        "",
        f"Measured single-chip phases (bench_details.json): inversion "
        f"{inv_s} s, edit {edit_s} s.",
        "",
        "Mesh: `--mesh 1,4,1` — 8 frames shard over 4 chips (sequence"
        " parallel); per-frame compute divides by 4; the two collective"
        " families below ride ICI. No compute/communication overlap is"
        " assumed (conservative).",
        "",
        "## Per-step ICI traffic per chip (edit batch, 3 streams)",
        "",
        "| site | instances | frame-0 KV broadcast | temporal all-gather/chip | total/chip/step |",
        "|---|---|---|---|---|",
    ]
    for r in traffic_table(3, 8, 4):
        lines.append(
            f"| {r['site']} | {r['instances']} | {r['kv_broadcast_mb']} MB "
            f"| {r['temporal_gather_mb_per_chip']} MB "
            f"| {r['total_mb_per_chip_per_step']} MB |"
        )
    lines += ["", "## Projection vs ICI bandwidth", "",
              "| effective ICI GB/s | projected e2e | parallel efficiency |",
              "|---|---|---|"]
    for bw in (50.0, 100.0, 200.0):
        p = project(inv_s, edit_s, ici_gbps=bw, **shard_kw)
        lines.append(
            f"| {bw:.0f} | {p['projected_v5e4_s']} s "
            f"| {p['parallel_efficiency']:.2f} |"
        )
    p = project(inv_s, edit_s, **shard_kw)
    lines += [
        "",
        "## Uncertainty: why the point estimate moved between rounds, and",
        "the range that replaces it",
        "",
        "The recorded efficiency swung 0.948 (r3) → 0.765 (r4) when the",
        "per-chip compute model switched from *linear-in-sp* (single-chip",
        "time ÷ 4 — assumes zero small-batch loss) to the *measured shard",
        "proxy* (the F/4-frame working point run on one chip — includes",
        "real small-batch loss AND host timing noise: the",
        "proxy phases are 2–4 s, where the observed ±0.3 s run-to-run",
        "wobble is ~15 %). Neither model is wrong; they bracket the truth:",
        "linear is the optimistic bound (a real mesh hides some per-chip",
        "overhead under collectives), the proxy is the pessimistic bound",
        "(host noise inflates short readings, and the proxy cannot",
        "overlap what a real mesh overlaps). The projection of record is",
        "therefore a RANGE over {both compute models} × {0.5×, 1×, 2× the",
        "conservative 100 GB/s effective ICI bandwidth}, and claims should",
        "quote the range, not three significant figures of either point:",
        "",
        f"**Range: {p['projected_v5e4_range_s'][0]}–"
        f"{p['projected_v5e4_range_s'][1]} s** for the live fast edit.",
        "",
        "North-star check (BASELINE.md: <10 s on v5e-4): evaluated at the",
        f"PESSIMISTIC end of the range — {p['projected_v5e4_range_s'][1]} s "
        + ("satisfies" if p["projected_v5e4_range_s"][1] < 10 else "MISSES")
        + " the target.",
    ]
    lines += [
        "",
        f"**Recorded projection (100 GB/s): {p['projected_v5e4_s']} s, "
        f"efficiency {p['parallel_efficiency']:.2f}"
        + (" — per-chip compute MEASURED via the 2-frame working point"
           f" (inversion {shard_kw['shard_inv_s']} s, edit"
           f" {shard_kw['shard_edit_s']} s)" if shard_kw else
           " — per-chip compute modeled as single-chip/4") + ".**",
        "",
        "Evidence trail: per-site shapes are the SD-1.5 topology"
        " (models/unet.py); the five N=4096 frame-attention instances per"
        " forward are visible in the xplane op table"
        " (tools/xplane_top_ops.py); sharded==unsharded correctness is"
        " tests/test_parallel.py; the sharded 32-frame controlled edit runs"
        " in the driver's multichip dryrun (__graft_entry__.py). The sharded"
        " path runs the SAME fused Pallas kernel per shard"
        " (parallel/mesh.py make_sharded_frame_attention_fn), so the 2-frame"
        " single-chip proxy measures the per-chip compute of the mesh"
        " faithfully.",
    ]
    docs = os.path.join(root, "docs")
    os.makedirs(docs, exist_ok=True)
    out_md = os.path.join(docs, "PROJECTION.md")
    with open(out_md, "w") as f:
        f.write("\n".join(lines) + "\n")

    # measured small-batch efficiency from the shard proxy: the ratio of the
    # ideal per-chip time (single-chip/sp) to the MEASURED F/sp-frame time;
    # reused to derate the configs that have no dedicated proxy
    eff = 1.0
    if shard_kw:
        ideal = (inv_s + edit_s) / 4
        measured = shard_kw["shard_inv_s"] + shard_kw["shard_edit_s"]
        if measured > 0:
            eff = min(1.0, ideal / measured)

    out = {"fast_edit_live": p}
    # the CLI's default fast path: cached-source (2-stream edit). No shard
    # proxy exists for it, so per-chip compute is linear-in-sp derated by
    # the efficiency the LIVE proxy measured; collectives use the 2-stream
    # traffic — the capture trees shard over frames, so base-map reads stay
    # chip-local (tests/test_parallel.py pins sharded==unsharded)
    if "inversion_s" in bd and "edit_s" in bd and "inversion_live_s" in bd:
        # true measured single-chip times in; the derate applies only to the
        # per-chip compute division inside project(), so single_chip_s and
        # parallel_efficiency in the evidence stay honest
        out["fast_edit_cached"] = project(
            bd["inversion_s"], bd["edit_s"], edit_streams=2, efficiency=eff,
        )
        out["fast_edit_cached"]["assumptions"]["compute_scaling"] = (
            f"linear in sp derated by the live shard proxy's measured "
            f"efficiency {eff:.2f}"
        )
    if "null_text_fixed3_s" in bd and "official_edit_s" in bd:
        out["official_edit"] = project_official(
            inv_s, bd["null_text_fixed3_s"], bd["official_edit_s"],
            efficiency=eff,
        )
    # r5 renamed the measured key (the 10-step extrapolation was retired);
    # keep the fallback so pre-r5 records still project
    long_s = bd.get("long24_fast_edit_e2e_s",
                    bd.get("long24_fast_edit_e2e_s_extrapolated"))
    if long_s is not None:
        out["long24_fast_edit"] = project_long(long_s, efficiency=eff)
        if "long24_mode" in bd:
            out["long24_fast_edit"]["assumptions"]["measured_mode"] = bd["long24_mode"]
    if "shard2_samples" in bd:
        out["shard_proxy_samples"] = bd["shard2_samples"]
    with open(os.path.join(docs, "projection_v5e4.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_md}")
    print(json.dumps({k: p[k] for k in ("projected_v5e4_s", "parallel_efficiency")}))


if __name__ == "__main__":
    main()
