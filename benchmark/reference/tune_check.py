"""The comparison that decides ``correct`` for a tuning cell: the program's
first ``train_steps`` call (the window's own call, ``steps_per_call`` steps
from the seed) against the plain reference following the same steps.

Numbers (each smaller is better). A cell compares those its file gives a
limit under ``limits``, and no others:
  loss_gap_first    |L_prog - L_ref| / L_ref at step 1 (forward pass alone)
  loss_gap_worst    the same, worst over the call's steps
  mu_gap_worst      worst leaf: | ||mu_prog|| - ||mu_ref|| | over the larger
                    of that leaf's and the median leaf's reference norm
                    (Adam's first moment: the recent gradients as the
                    optimizer got them, after clipping)
  nu_gap_worst      the same for the second moment (all the call's gradients)
  change_gap_worst  the same for the parameters' change over the call; leaves
                    whose reference gradient is nought to rounding (sqrt-nu
                    norm under a thousandth of the median leaf's) are left out
  mu_diff_worst, mu_diff_median, change_diff_worst, change_diff_median
                    || x_prog - x_ref || of a leaf over the same denominator:
                    the norm of the DIFFERENCE, which sees a gradient that
                    points elsewhere at the right length (half of the clip
                    left out of the loss), where a gap of norms does not;
                    worst leaf, and the median leaf (steady from seed to seed)
  frozen_moved      how many frozen leaves differ from their initial values
                    (exact, limit 0)
  calls_not_finite  windowed calls whose losses hold a NaN or Inf (exact)
"""

from __future__ import annotations

import time

import numpy as np

TINY_ARCH = {"block_out_channels": (8, 16), "layers_per_block": 1,
             "heads": 2, "groups": 4,
             "down": ("CrossAttnDownBlock3D", "DownBlock3D"),
             "up": ("UpBlock3D", "CrossAttnUpBlock3D")}


def _norm(x) -> float:
    import jax.numpy as jnp

    return float(jnp.sqrt(jnp.sum(jnp.asarray(x, jnp.float32) ** 2)))


def _leaf_numbers(prog: dict, ref: dict, keep=None):
    """Per leaf, over max(that leaf's, the median leaf's reference norm):
    the gap of the norms and the norm of the difference. Returns
    ``{"gap_worst", "diff_worst", "diff_median"}`` and where the worst are."""
    import jax.numpy as jnp

    r_norm = {k: _norm(v) for k, v in ref.items()}
    med = float(np.median(list(r_norm.values())))
    gap, diff = {}, {}
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        den = max(r_norm[k], med, 1e-30)
        p = jnp.asarray(prog[k], jnp.float32)
        gap[k] = abs(_norm(p) - r_norm[k]) / den
        diff[k] = _norm(p - jnp.asarray(r, jnp.float32)) / den
    at_gap, at_diff = max(gap, key=gap.get), max(diff, key=diff.get)
    return ({"gap_worst": gap[at_gap], "diff_worst": diff[at_diff],
             "diff_median": float(np.median(list(diff.values())))},
            {"gap": at_gap, "diff": at_diff})


def gaps(prog: dict, ref: dict, init: dict) -> dict:
    """``prog``/``ref``: {"losses", "trainable", "mu", "nu"} by leaf name;
    ``init``: the trainable leaves' initial values."""
    import jax.numpy as jnp

    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(
        ref["losses"], np.float64)
    n = min(len(lp), len(lr))
    rel = np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n])
    out = {"loss_gap_first": float(rel[0]), "loss_gap_worst": float(rel.max())}
    mu, at_mu = _leaf_numbers(prog["mu"], ref["mu"])
    nu, at_nu = _leaf_numbers(prog["nu"], ref["nu"])
    # a leaf whose reference gradient is nought to rounding moves under Adam
    # by round-off alone: leave it out of the change, by a rule on the
    # reference's gradient (sqrt of the second moment), not by name
    g_ref = {k: float(jnp.sqrt(jnp.sum(v))) for k, v in ref["nu"].items()}
    g_med = float(np.median(list(g_ref.values())))
    keep = {k for k, g in g_ref.items() if g >= 1e-3 * g_med}
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    d_prog = {k: f32(prog["trainable"][k]) - f32(init[k]) for k in init}
    d_ref = {k: f32(ref["trainable"][k]) - f32(init[k]) for k in init}
    change, at_d = _leaf_numbers(d_prog, d_ref, keep)
    out.update(mu_gap_worst=mu["gap_worst"], nu_gap_worst=nu["gap_worst"],
               change_gap_worst=change["gap_worst"],
               mu_diff_worst=mu["diff_worst"],
               mu_diff_median=mu["diff_median"],
               change_diff_worst=change["diff_worst"],
               change_diff_median=change["diff_median"])
    out["_where"] = {"mu": at_mu, "nu": at_nu, "change": at_d,
                     "left_out_of_change": sorted(set(g_ref) - keep)}
    return out


def reference_weights(seed) -> dict:
    """The UNet's seeded weights again, by leaf name — made by the
    benchmark's generator, not read from the program."""
    from benchmark.harness import steer
    from benchmark.harness.weights import flatten_named

    return {k: v for k, v in flatten_named(
        steer.regenerate("unet", seed)).items() if k.startswith("params/")}


def arch_for(config: dict, rehearse: bool) -> dict:
    from benchmark.reference.unet3d import arch_from_config

    return TINY_ARCH if rehearse else arch_from_config(config)


def compared(cell: dict, g: dict, frozen_moved, calls_not_finite,
             rehearse: bool = False) -> dict:
    """``{name: {"value", "limit"}}`` for every number the cell's file gives
    a limit. A rehearsal at the tiny preset takes the file's
    ``rehearse_limits`` over them (eight channels round far more coarsely
    than 320-1280 do)."""
    have = dict(g, frozen_moved=frozen_moved,
                calls_not_finite=calls_not_finite)
    limits = dict(cell["limits"],
                  **(cell.get("rehearse_limits", {}) if rehearse else {}))
    return {k: {"value": have.get(k), "limit": limit}
            for k, limit in limits.items()}


def run_check(*, config, cell, seed, prog, init_trainable, frozen_final,
              latents, text, run_key, n_steps, calls_not_finite, rehearse,
              note) -> dict:
    """Regenerate the seeded weights, follow the first call's steps with the
    plain reference, and return ``{name: {"value", "limit"}}``."""
    import jax.numpy as jnp

    from benchmark.reference import train

    flat = reference_weights(seed)
    note({"phase": "weights_regenerated"})
    # the regenerated leaves are the ones the program started from
    for k, v in init_trainable.items():
        assert bool(jnp.array_equal(flat[k], v)), f"regenerated {k} differs"
    moved = sum(not bool(jnp.array_equal(flat[k], v))
                for k, v in frozen_final.items())
    frozen_final.clear()
    t0 = time.perf_counter()
    ref = train.tune(flat, arch_for(config, rehearse), config["training"],
                     latents, text, run_key, n_steps)
    note({"phase": "reference", "steps": n_steps,
          "s": round(time.perf_counter() - t0, 2),
          "loss_ref": [round(float(x), 6) for x in ref["losses"][:3]],
          "loss_prog": [round(float(x), 6) for x in prog["losses"][:3]]})
    g = gaps(prog, ref, init_trainable)
    note({"phase": "gaps", **g})
    return compared(cell, g, moved, calls_not_finite, rehearse)
