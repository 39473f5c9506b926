"""Least time the chip could take for a kernel's WORK, from shapes.

``fused_frame_attention`` (ops/attention.py): per call site of one forward,
queries (F, H, N, D) against frame 0's keys and values (H, N, D):
operations 4 F H N N D; bytes: q and o (F H N D each), k and v (H N D each)
at the compute type's width. The least time is the larger of operations
over peak FLOP/s and bytes over peak bytes/s.

``fused_group_norm`` has no roofline here: its slabs are served from on-chip
memory (layout ``S(1)`` in the trace), and one read + one write of the slab
over the published HBM bandwidth read 150 % (PERF.md, PR 25)."""

from __future__ import annotations

from benchmark.harness import flops


def frame_attention_site(frames: int, r: int, c: int, itemsize: int = 2):
    """(operations, bytes) of one frame-attention call site, one stream."""
    n = r * r
    ops = 4.0 * frames * n * n * c          # = 4 F H N N D, H D = c
    nbytes = float(itemsize) * (2 * frames * n * c + 2 * n * c)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peaks: dict):
    """(seconds, which bound binds)."""
    t_ops = ops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def frame_attention_forward(cfg: dict, frames: int, latent: int,
                            peaks: dict) -> dict:
    """Least seconds of all frame-attention sites of ONE forward of one
    stream, and how many sites each bound binds."""
    total, binds = 0.0, {"compute": 0, "memory": 0}
    for r, c in flops.attention_sites(cfg, latent):
        t, which = least_seconds(*frame_attention_site(frames, r, c), peaks)
        total += t
        binds[which] += 1
    return {"seconds": total, "binds": binds}
