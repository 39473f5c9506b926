"""The comparison that decides ``correct`` for Keye-VL-2.0's tuning cell: the
program's first ``train_steps`` call (``steps_per_call`` steps from the
seed, the same document every step) against the plain float32 reference
following the same steps (``reference/keye_vl2.py``: explicit selection
masks, mRoPE from its three position rows, the experts one by one).
Everything of the program's that is compared is an output of that one timed
call. The other token cells' scheme (``tune_lm_check.py``), in two halves,
because a top-k is discontinuous and a near-tie that flips under bfloat16 is
no fault:

*The state, GIVEN the program's choices.* The reference takes the selection
and the experts a token that every layer of the program chose at every step
and follows the steps in float32; the tune cells' state comparisons
(``tune_check.gaps``) then read arithmetic alone. This layer has no shared
expert, so there is no ``routed_share_gap``: the gates' scale is held by the
state itself.

*The choices, against the reference's own* at the initial weights:
  selected_key_diff    share of the keys the program selected, over all
                       layers and queries, that the reference did not select
  expert_choice_diff   share of the program's (token, expert) choices, over
                       all layers, that are not among the reference's
                       experts for that token
with limits from readings, not 0. ``frozen_moved`` compares fingerprints
(``weights_lm.fingerprints``) of the frozen leaves the program handed back
with those of the regenerated weights.
"""

from __future__ import annotations

import time

TINY_ARCH = {
    "hidden_size": 64, "moe_intermediate_size": 32, "head_dim": 16,
    "num_hidden_layers": 2, "num_attention_heads": 8, "num_key_value_heads": 2,
    "num_experts": 8, "num_experts_per_tok": 3, "rms_norm_eps": 1e-6,
    "rope_theta": 1e7, "vocab_size": 256, "mrope_section": (2, 3, 3),
    "indexer_num_heads": 4, "indexer_head_dim": 8, "topk": 16,
    "experts_held": (0, 8),
}


def choice_gaps(prog: list, own: list) -> dict:
    """``prog``: per layer what the program chose at the first step,
    ``{"mask": (T, T / 8) uint8, "experts": (T, K)}``; ``own``: what the
    reference chose for itself there."""
    import jax
    import jax.numpy as jnp

    def count(bits):
        return float(jnp.sum(jax.lax.population_count(bits), dtype=jnp.float32))

    extra = sum(count(jnp.asarray(p["mask"]) & ~jnp.asarray(r["mask"]))
                for p, r in zip(prog, own))
    selected = sum(count(jnp.asarray(p["mask"])) for p in prog)
    missed, pairs = 0.0, 0
    for p, r in zip(prog, own):
        among = (jnp.asarray(p["experts"])[:, :, None]
                 == jnp.asarray(r["experts"])[:, None, :]).any(-1)
        missed += float(jnp.sum(~among))
        pairs += among.size
    return {"selected_key_diff": extra / max(selected, 1.0),
            "expert_choice_diff": missed / max(pairs, 1)}


def arch_for(config: dict, rehearse: bool) -> dict:
    from benchmark.reference.keye_vl2 import arch_from_config

    return TINY_ARCH if rehearse else arch_from_config(config)


def run_check(*, config, cell, seed, prog, rows, init_trainable,
              frozen_prints, ids, n_steps, calls_not_finite, rehearse, note,
              **how) -> dict:
    """Regenerate the seeded weights (with the router ``rows`` the run was
    given), follow the first ``n_steps`` steps of the first call with the
    plain reference GIVEN ``prog["choices"]``, and return ``{name: {"value",
    "limit"}}``. ``how`` passes a planted fault or a control precision to the
    reference."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import weights_keye, weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import keye_vl2, tune_check

    flat = flatten_named(weights_keye.regenerate(seed, rows=rows))
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
    ref = keye_vl2.tune(
        flat, arch_for(config, rehearse), config["training"], ids, n_steps,
        given=prog["choices"][:n_steps], remat=not rehearse,
        row_block=None if rehearse else cell["reference_row_block"], **how)
    note({"phase": "reference", "steps": n_steps,
          "s": round(time.perf_counter() - t0, 2),
          "loss_ref": [round(float(x), 6) for x in ref["losses"]],
          "loss_prog": [round(float(x), 6) for x in prog["losses"]]})
    if not np.isfinite(ref["losses"]).all():
        g = dict.fromkeys(cell["limits"], float("nan"))
    else:
        g = tune_check.gaps(prog, ref, init_trainable)
        g.update(choice_gaps(prog["choices"][0], ref["chosen_own"]))
    note({"phase": "gaps", **g})
    return tune_check.compared(cell, g, moved, calls_not_finite, rehearse)
