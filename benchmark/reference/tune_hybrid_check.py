"""The comparison that decides ``correct`` for the hybrid token model's
tuning cell: the program's first ``train_steps`` call (``steps_per_call``
steps from the seed, the same document every step) against the plain
float32 reference following the same steps
(``reference/granite_moe_hybrid.py``: the state-space recurrence token by
token). Everything of the program's that is compared is an output of that
one timed call. The other token cell's scheme (``tune_lm_check.py``), in
two halves, because a top-k is discontinuous and a near-tie that flips
under bfloat16 is no fault:

*The state, GIVEN the program's choices.* The reference takes the experts a
token that every layer of the program chose at every step and follows the
steps in float32; the tune cells' state comparisons (``tune_check.gaps``)
then read arithmetic alone, and beside them
  routed_share_gap   worst layer at the first step: | r_prog - r_ref | /
                     r_ref of r = the held experts' part over the shared
                     expert's (root mean square over the document): it
                     carries the gates
  state_rms_gap      | s_prog - s_ref | / max(s_prog, s_ref) of s = the root
                     mean square, over the last ``mamba_chunk_size`` tokens, of what the
                     scan's outputs owe to the state before those tokens,
                     mean over the Mamba layers, at the first step: the
                     program's counter ``ssd_state_rms`` (the term its last
                     chunk adds from the state it was handed) against the
                     recurrence's own (its outputs less the same tokens'
                     from a zero state). A side that hands nothing on reads
                     0, and the gap is then 1

*The choices, against the reference's own* at the initial weights:
  expert_choice_diff share of the program's (token, expert) choices, over
                     all layers, that are not among the reference's experts
                     for that token
with limits from readings, not 0. ``frozen_moved`` compares fingerprints
(``weights_lm.fingerprints``) of the frozen leaves the program handed back
with those of the regenerated weights.
"""

from __future__ import annotations

import time

TINY_ARCH = {
    "hidden_size": 64, "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_hidden_layers": 3, "layer_types": ("mamba", "attention", "mamba"),
    "attention_multiplier": 0.25, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "logits_scaling": 16.0, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "num_experts_per_tok": 3, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "num_local_experts": 8, "head_dim": 8, "experts_held": (0, 8),
    "heads_held": (0, 8), "kv_heads_held": (0, 4), "mamba_heads_held": (0, 16),
}


def choice_gaps(prog: list, own: list, followed: list, state_rms: float) -> dict:
    """``prog``: per layer what the program chose at the first step
    (``{"experts": (T, K), "routed_over_shared"}``); ``own``: what the
    reference chose for itself there; ``followed``: the reference's first
    step GIVEN the program's choices (its ``routed_over_shared`` and
    ``state_rms``); ``state_rms``: the program's counter at that step."""
    import jax.numpy as jnp
    import numpy as np

    missed, pairs, share = 0.0, 0, 0.0
    for p, r, f in zip(prog, own, followed):
        among = (jnp.asarray(p["experts"])[:, :, None]
                 == r["experts"][:, None, :]).any(-1)
        missed += float(jnp.sum(~among))
        pairs += among.size
        want = float(f["routed_over_shared"])
        share = max(share, abs(float(p["routed_over_shared"]) - want) / want)
    want = float(np.mean([float(f["state_rms"]) for f in followed
                          if f["state_rms"] is not None]))
    return {"expert_choice_diff": missed / max(pairs, 1),
            "routed_share_gap": share,
            "state_rms_gap": (abs(state_rms - want)
                              / max(state_rms, want, 1e-30))}


def arch_for(config: dict, rehearse: bool) -> dict:
    from benchmark.reference.granite_moe_hybrid import arch_from_config

    return TINY_ARCH if rehearse else arch_from_config(config)


def run_check(*, config, cell, seed, prog, rows, init_trainable,
              frozen_prints, ids, n_steps, calls_not_finite, rehearse, note, **how) -> dict:
    """Regenerate the seeded weights (with the router ``rows`` the run was
    given), follow the first ``n_steps`` steps of
    the first call with the plain reference GIVEN ``prog["choices"]``, and
    return ``{name: {"value", "limit"}}``. ``how`` passes a planted fault or
    a control precision to the reference."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import weights_hybrid, weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import granite_moe_hybrid, tune_check

    flat = flatten_named(weights_hybrid.regenerate(seed, rows=rows))
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
    ref = granite_moe_hybrid.tune(
        flat, arch_for(config, rehearse), config["training"], ids, n_steps,
        given=prog["choices"][:n_steps], remat=not rehearse,
        row_block=None if rehearse else cell["reference_row_block"], **how)
    note({"phase": "reference", "steps": n_steps,
          "s": round(time.perf_counter() - t0, 2),
          "loss_ref": [round(float(x), 6) for x in ref["losses"]],
          "loss_prog": [round(float(x), 6) for x in prog["losses"]]})
    if not np.isfinite(ref["losses"]).all():
        # a planted fault that overflows (step sizes without their
        # softplus): every number reads NaN, which no limit admits
        g = dict.fromkeys(cell["limits"], float("nan"))
    else:
        g = tune_check.gaps(prog, ref, init_trainable)
        g.update(choice_gaps(prog["choices"][0], ref["chosen_own"],
                             ref["chosen"], prog["state_rms_first"]))
    note({"phase": "gaps", **g})
    return tune_check.compared(cell, g, moved, calls_not_finite, rehearse)
