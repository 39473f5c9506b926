"""The comparison that decides ``correct`` for the third token family's
tuning cell: the program's first ``train_steps`` call (``steps_per_call``
steps from the seed, the same document every step) against the plain
float32 reference following the same steps (``reference/cohere2_moe.py``:
explicit masks, the shared experts one by one). Everything of the program's
that is compared is an output of that one timed call. The other token
cells' scheme (``tune_hybrid_check.py``), in two halves, because a top-k is
discontinuous and a near-tie that flips under bfloat16 is no fault:

*The state, GIVEN the program's choices.* The reference takes the experts a
token that every layer of the program chose at every step and follows the
steps in float32; the tune cells' state comparisons (``tune_check.gaps``)
then read arithmetic alone, and beside them
  routed_share_gap   worst layer at the first step: | r_prog - r_ref | /
                     r_ref of r = the held experts' part over the shared
                     experts' averaged part (root mean square over the
                     document): it carries the gates and the average

*The choices, against the reference's own* at the initial weights:
  expert_choice_diff share of the program's (token, expert) choices, over
                     all layers, that are not among the reference's experts
                     for that token
with limits from readings, not 0. ``frozen_moved`` compares fingerprints
(``weights_lm.fingerprints``) of the frozen leaves the program handed back
with those of the regenerated weights.

*The window's edge, on a probe* — the ONE number that is not an output of
the timed call, because one key of 4096 moves every number above by less
than bfloat16 does (the planted fault ``window_4095`` read inside the sound
band on all of them, PERF.md section 6, PR 34):
  window_edge_gap    the program's own ``attention()`` of a sliding layer
                     (the family's function as the timed program calls it:
                     the same shapes, so on the TPU the same kernel pair on
                     the same tiles) against the reference's, on an input
                     made so that ONE key counts: token t is the unit vector
                     of its class t mod window, the query projection is 0
                     (every visible key weighs the same), and the value and
                     output projections are +-1 with the classes summing to
                     0 — so a window of exactly ``sliding_window`` keys
                     gives 0 to the last bit in every row past the first
                     window, output and input gradient alike, and a window
                     one key short or long gives that key's (or that
                     query's) part, whose size is the unit: the worst row's
                     | prog - ref | over (| ref | + one key's part).
"""

from __future__ import annotations

import time

TINY_ARCH = {
    "hidden_size": 64, "intermediate_size": 32, "head_dim": 16,
    "num_hidden_layers": 4,
    "layer_types": ("sliding_attention",) * 3 + ("full_attention",),
    "sliding_window": 8, "rope_theta": 50000.0, "num_experts_per_tok": 3,
    "layer_norm_eps": 1e-5, "logit_scale": 1.0, "vocab_size": 256,
    "num_experts": 8, "num_shared_experts": 2, "experts_held": (0, 8),
    "heads_held": (0, 8), "kv_heads_held": (0, 2),
    "shared_columns_held": (0, 64),
}


def choice_gaps(prog: list, own: list, followed: list) -> dict:
    """``prog``: per layer what the program chose at the first step
    (``{"experts": (T, K), "routed_over_shared"}``); ``own``: what the
    reference chose for itself there; ``followed``: the reference's first
    step GIVEN the program's choices (its ``routed_over_shared``)."""
    import jax.numpy as jnp

    missed, pairs, share = 0.0, 0, 0.0
    for p, r, f in zip(prog, own, followed):
        among = (jnp.asarray(p["experts"])[:, :, None]
                 == r["experts"][:, None, :]).any(-1)
        missed += float(jnp.sum(~among))
        pairs += among.size
        want = float(f["routed_over_shared"])
        share = max(share, abs(float(p["routed_over_shared"]) - want) / want)
    return {"expert_choice_diff": missed / max(pairs, 1),
            "routed_share_gap": share}


def edge_probe(arch: dict, n_tokens: int):
    """``(u (T, h) float32, {leaf: float32 array})``: token t the unit
    vector of its class ``t mod window``; ``q_proj`` 0; ``k_proj`` seeded
    normal; ``v_proj``'s rows and ``o_proj``'s columns of the ``window``
    classes +-1, each output's over the classes summing to 0."""
    import numpy as np

    h, hd, w = arch["hidden_size"], arch["head_dim"], arch["sliding_window"]
    hq, hkv = arch["heads_held"][1] * hd, arch["kv_heads_held"][1] * hd
    assert w % 2 == 0 and w <= h and n_tokens > 2 * w, (w, h, n_tokens)
    rng = np.random.default_rng(0)

    def balanced(n):  # (n, w) of +-1, every row summing to 0
        return rng.permuted(np.tile([1.0, -1.0], (n, w // 2)), axis=1)

    u = np.zeros((n_tokens, h), np.float32)
    u[np.arange(n_tokens), np.arange(n_tokens) % w] = 1.0
    v_proj, o_proj = np.zeros((h, hkv), np.float32), np.zeros((hq, h), np.float32)
    v_proj[:w], o_proj[:, :w] = balanced(hkv).T, balanced(hq)
    return u, {"q_proj/kernel": np.zeros((h, hq), np.float32),
               "k_proj/kernel": (rng.standard_normal((h, hkv)) / h ** 0.5
                                 ).astype(np.float32),
               "v_proj/kernel": v_proj, "o_proj/kernel": o_proj}


def window_edge_gap(program_cfg, arch: dict, n_tokens: int, *,
                    operand="float32", fault=None, remat=False,
                    row_block=None) -> float:
    """The worst row of the program's sliding ``attention()`` against the
    reference's on :func:`edge_probe` — the layer's output and its gradient
    in the input for the cotangent ``u`` — in units of one key's part."""
    import jax
    import jax.numpy as jnp

    from videop2p_tpu.models import cohere2_moe as program

    from benchmark.reference import cohere2_moe as ref

    u, leaves = edge_probe(arch, n_tokens)

    @jax.jit
    def prog(u, leaves):
        p = {k.split("/")[0]: {"kernel": v.astype(jnp.bfloat16)}
             for k, v in leaves.items()}
        angles = program.rope_angles(program_cfg, jnp.arange(n_tokens))
        u = u.astype(jnp.bfloat16)
        y, pull = jax.vjp(
            lambda u: program.attention(p, program_cfg, u, angles)[0], u)
        return y, pull(u)[0]

    @jax.jit
    def plain(u, leaves):
        with jax.default_matmul_precision("highest"):
            y, pull = jax.vjp(lambda u: ref.attention_part(
                ref.Weights(leaves, ""), arch, ref._Nx(operand), u,
                ref.SLIDING, fault=fault, remat=remat, row_block=row_block), u)
            return y, pull(u)[0]

    hq = arch["heads_held"][1] * arch["head_dim"]
    one_key = (hq / arch["sliding_window"]) ** 0.5
    norm = lambda a: jnp.sqrt(jnp.sum(  # noqa: E731
        jnp.square(a.astype(jnp.float32)), axis=-1))
    return max(float(jnp.max(norm(a.astype(jnp.float32) - b)
                             / (norm(b) + one_key)))
               for a, b in zip(prog(u, leaves), plain(u, leaves)))


def arch_for(config: dict, rehearse: bool) -> dict:
    from benchmark.reference.cohere2_moe import arch_from_config

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

    from benchmark.harness import weights_command, weights_lm
    from benchmark.harness.weights import flatten_named
    from benchmark.reference import cohere2_moe, tune_check

    how_ref = dict(how, remat=not rehearse,
                   row_block=None if rehearse else cell["reference_row_block"])
    # first, while the device is empty: the window's edge on its probe
    edge = window_edge_gap(weights_command.REGEN["args"][0],
                           arch_for(config, rehearse), int(ids.shape[0]),
                           **how_ref)
    note({"phase": "window_edge", "window_edge_gap": edge})
    flat = flatten_named(weights_command.regenerate(seed, rows=rows))
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
    ref = cohere2_moe.tune(
        flat, arch_for(config, rehearse), config["training"], ids, n_steps,
        given=prog["choices"][:n_steps], **how_ref)
    note({"phase": "reference", "steps": n_steps,
          "s": round(time.perf_counter() - t0, 2),
          "loss_ref": [round(float(x), 6) for x in ref["losses"]],
          "loss_prog": [round(float(x), 6) for x in prog["losses"]]})
    if not np.isfinite(ref["losses"]).all():
        g = dict.fromkeys(cell["limits"], float("nan"))
    else:
        g = tune_check.gaps(prog, ref, init_trainable)
        g.update(choice_gaps(prog["choices"][0], ref["chosen_own"],
                             ref["chosen"]), window_edge_gap=edge)
    note({"phase": "gaps", **g})
    return tune_check.compared(cell, g, moved, calls_not_finite, rehearse)
