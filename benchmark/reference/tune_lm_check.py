"""The comparison that decides ``correct`` for a token-model tuning cell:
the program's first ``train_steps`` call (``steps_per_call`` steps from the
seed, the same document every step) against the plain float32 reference
following the same steps (``reference/deepseek_v32.py``). Everything of the
program's that is compared is an output of that one timed call.

Top-k is discontinuous: a near-tie flips under bfloat16 and is no fault, and
a flipped key or expert is another function of the weights (handed its own
choices the reference's parameter change differs from the program's by 0.6
of its norm; handed the program's, by a tenth: PERF.md section 6, PR 28). So
the comparison has two halves.

*The state, GIVEN the program's choices.* The reference takes what every
layer of the program chose at every step (``given``) and follows the steps
in float32; the tune cell's state comparisons (``tune_check.gaps``: loss
gaps, moment gaps, change gaps and differences, ``frozen_moved``,
``calls_not_finite``) then read arithmetic alone, and
  routed_share_gap     worst expert layer at the first step:
                       | r_prog - r_ref | / r_ref of r = the held experts'
                       part over the shared expert's (root mean square over
                       the document): it carries the gates' scale

*The choices, against the reference's own* at the initial weights:
  expert_choice_diff   share of the program's (token, expert) choices, over
                       the expert layers, that are not among the reference's
                       experts for that token
  selected_key_diff    share of the keys the program selected, over all
                       layers and queries, that the reference did not select
with limits from readings, not 0. ``frozen_moved`` compares fingerprints
(``weights_lm.fingerprints``) of the frozen leaves the program handed back
with those of the regenerated weights: the frozen share is 7.7 GB and a
second copy does not fit beside it.
"""

from __future__ import annotations

import time

TINY_ARCH = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "first_k_dense_replace": 2, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 16, "n_routed_experts": 8, "n_group": 2, "topk_group": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.5, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "experts_held": (0, 8), "heads_held": (0, 4),
}


def choice_gaps(prog: list, own: list, followed: list) -> dict:
    """``prog``: per layer what the program chose at the first step,
    ``{"mask": (T, T / 8) uint8, the selection eight keys a byte, "experts":
    (T, K) or None, "routed_over_shared"}``; ``own``: what the reference
    chose for itself there; ``followed``: the reference's first step GIVEN
    the program's choices (its ``routed_over_shared``)."""
    import jax
    import jax.numpy as jnp

    def count(bits):
        return float(jnp.sum(jax.lax.population_count(bits), dtype=jnp.float32))

    extra = sum(count(jnp.asarray(p["mask"]) & ~r["mask"])
                for p, r in zip(prog, own))
    selected = sum(count(jnp.asarray(p["mask"])) for p in prog)
    missed, pairs, share = 0.0, 0, 0.0
    for p, r, f in zip(prog, own, followed):
        if r["experts"] is None:
            continue
        among = (jnp.asarray(p["experts"])[:, :, None]
                 == r["experts"][:, None, :]).any(-1)
        missed += float(jnp.sum(~among))
        pairs += among.size
        want = float(f["routed_over_shared"])
        share = max(share, abs(float(p["routed_over_shared"]) - want) / want)
    return {"selected_key_diff": extra / max(selected, 1.0),
            "expert_choice_diff": missed / max(pairs, 1),
            "routed_share_gap": share}


def arch_for(config: dict, rehearse: bool) -> dict:
    from benchmark.reference.deepseek_v32 import arch_from_config

    return TINY_ARCH if rehearse else arch_from_config(config)


def run_check(*, config, cell, seed, prog, biases, init_trainable,
              frozen_prints, ids, n_steps, calls_not_finite, rehearse, note,
              **how) -> dict:
    """Regenerate the seeded weights (with the selection ``biases`` the run
    was given), follow the first call's steps with the
    plain reference GIVEN ``prog["choices"]``, and return ``{name: {"value",
    "limit"}}``. ``how`` passes a planted fault or a control precision to
    the reference."""
    import jax.numpy as jnp

    from benchmark.harness import weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import deepseek_v32, tune_check

    flat = flatten_named(weights_lm.regenerate(seed, biases=biases))
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
    ref = deepseek_v32.tune(
        flat, arch_for(config, rehearse), config["training"], ids, n_steps,
        layerwise=True, given=prog["choices"], remat=not rehearse,
        row_block=None if rehearse else cell["reference_row_block"], **how)
    note({"phase": "reference", "steps": n_steps,
          "s": round(time.perf_counter() - t0, 2),
          "loss_ref": [round(float(x), 6) for x in ref["losses"]],
          "loss_prog": [round(float(x), 6) for x in prog["losses"]]})
    g = tune_check.gaps(prog, ref, init_trainable)
    g.update(choice_gaps(prog["choices"][0], ref["chosen_own"], ref["chosen"]))
    note({"phase": "gaps", **g})
    return tune_check.compared(cell, g, moved, calls_not_finite, rehearse)
