"""The comparison that decides ``correct`` for Falcon-H1-34B's tuning cell:
the program's first ``train_steps`` call (``steps_per_call`` steps from the
seed, the same document every step) against the plain float32 reference
following the same steps (``reference/falcon_h1.py``: the state-space
recurrence token by token per group, full-row attention, every multiplier
where the published code puts it). Everything of the program's that is
compared is an output of that one timed call. There is no discrete choice
to hand over (no router, no selection), so the state comparisons
(``tune_check.gaps``) read the whole computation, and beside them, at the
first step:
  state_rms_gap      | s_prog - s_ref | / max(s_prog, s_ref) of s = the root
                     mean square, over the last ``mamba_chunk_size`` tokens,
                     of what the scan's outputs owe to the state before those
                     tokens, mean over the layers: the program's counter
                     ``ssd_state_rms`` against the recurrence's own (its
                     outputs less the same tokens' from a zero state). A side
                     that hands nothing on reads 0, and the gap is then 1
  branch_ratio_gap   | r_prog - r_ref | / r_ref of r = the attention
                     branch's contribution over the mixer branch's, in root
                     mean square after their out-multipliers, mean over the
                     layers: the program's counter ``branch_rms_ratio``. A
                     misplaced multiplier moves it by orders of magnitude
``frozen_moved`` compares fingerprints (``weights_lm.fingerprints``) of the
frozen leaves the program handed back with those of the regenerated weights.
"""

from __future__ import annotations

import time

TINY_ARCH = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 1e4, "attention_in_multiplier": 1.0,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804,
    "embedding_multiplier": 5.656854249492381, "lm_head_multiplier": 0.0078125,
    "mlp_multipliers": (0.1767766952966369, 0.011160714285714284),
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738),
    "ssm_out_multiplier": 0.08838834764831845, "mamba_chunk_size": 8,
    "mamba_d_conv": 4, "mamba_d_head": 8, "mamba_d_ssm": 64,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_n_heads": 8,
    "rms_norm_eps": 1e-5, "vocab_size": 256,
}


def counter_gaps(prog: dict, readings: list) -> dict:
    """``prog``: the program's counters at the first step; ``readings``: the
    reference's first step, per layer."""
    import numpy as np

    state = float(np.mean([r["state_rms"] for r in readings]))
    ratio = float(np.mean([r["branch_rms_ratio"] for r in readings]))
    mine_state, mine_ratio = prog["ssd_state_rms"], prog["branch_rms_ratio"]
    return {"state_rms_gap": (abs(mine_state - state)
                              / max(mine_state, state, 1e-30)),
            "branch_ratio_gap": abs(mine_ratio - ratio) / max(ratio, 1e-30)}


def arch_for(config: dict, rehearse: bool) -> dict:
    from benchmark.reference.falcon_h1 import arch_from_config

    return TINY_ARCH if rehearse else arch_from_config(config)


def run_check(*, config, cell, seed, prog, init_trainable, frozen_prints, ids,
              n_steps, calls_not_finite, rehearse, note, **how) -> dict:
    """Regenerate the seeded weights, follow the first ``n_steps`` steps of
    the first call with the plain reference, and return ``{name: {"value",
    "limit"}}``. ``how`` passes a planted fault or a control precision to
    the reference."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import weights_falcon, weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import falcon_h1, tune_check

    flat = flatten_named(weights_falcon.regenerate(seed))
    note({"phase": "weights_regenerated"})
    # the regenerated leaves are the ones the program started from
    for k, v in init_trainable.items():
        assert bool(jnp.array_equal(flat[k].astype(jnp.float32), v)), \
            f"regenerated {k} differs"
    again = weights_lm.fingerprints({k: flat[k] for k in frozen_prints})
    moved = sum(again[k] != v for k, v in frozen_prints.items())
    t0 = time.perf_counter()
    # the reference takes the weights over (its frozen share moves to the
    # host: ``flat`` is emptied) and applies the chain rule layer by layer
    ref = falcon_h1.tune(
        flat, arch_for(config, rehearse), config["training"], ids, n_steps,
        remat=not rehearse,
        row_block=None if rehearse else cell["reference_row_block"], **how)
    note({"phase": "reference", "steps": n_steps,
          "s": round(time.perf_counter() - t0, 2),
          "loss_ref": [round(float(x), 6) for x in ref["losses"]],
          "loss_prog": [round(float(x), 6) for x in prog["losses"]],
          "readings_ref": ref["readings"],
          "counters_prog": prog["counters_first"]})
    if not np.isfinite(ref["losses"]).all():
        g = dict.fromkeys(cell["limits"], float("nan"))
    else:
        g = tune_check.gaps(prog, ref, init_trainable)
        g.update(counter_gaps(prog["counters_first"], ref["readings"]))
    note({"phase": "gaps", **g})
    return tune_check.compared(cell, g, moved, calls_not_finite, rehearse)
