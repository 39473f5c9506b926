"""Operation counts of the third token family (Command A+'s block, one chip's
share) from the configuration's shapes, and the windowed attention pair's.

Counts the WORK, never the implementation (``harness/flops.py``'s
convention): multiply-adds x 2 of every matrix product the architecture
defines for the heads, experts, shared columns and vocabulary rows HELD
here; a sliding layer's two attention products at the BANDED pairs
(0 <= t - s < window), a full layer's at the causal pairs (s <= t); routed
experts at the (token, expert) pairs that land on held experts
(``held_pair_share``: the program's counter where given, else the uniform
share held / published); the shared experts at the columns held; no norms,
softmax, rotary, activations, top-k, no padding, no masked tile and no
recompute.

``command_ops`` returns one record per product (``flops_lm.lm_ops``'s
form): site, fwd, act_operands_with_grad, weight_grad; the tuning step's
count is ``flops.tune_step_flops``. Nothing upstream of the first trainable
leaf carries a gradient: layer 0's input is the frozen embedding and ONE
norm feeds the whole parallel block, so of layer 0 only ``q_proj`` and what
the queries feed do.

``window_attention_pair_flops`` is the roofline's numerator: a sliding
layer's banded products as the kernel pair has to do them — 2 forward
(Q K^T, P V) + 4 backward (dV, dP, dQ, dK; the scores' recompute inside the
backward kernel is no useful work), over the query heads held, once a layer
and step however often a layer's recompute runs the forward kernel.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def banded_pairs(tokens: int, window: int) -> float:
    """(t, s) with 0 <= t - s < window, t < tokens."""
    w = min(int(window), int(tokens))
    return w * (w + 1) / 2.0 + (tokens - w) * float(w)


def causal_pairs(tokens: int) -> float:
    return tokens * (tokens + 1) / 2.0


def command_ops(cfg: dict, tokens: int, held_pair_share: float = None) -> list:
    """One document's forward, product by product. ``cfg``: the
    configuration file (published keys; the counts ``reduced`` names as
    HELD)."""
    t, h, hd = float(tokens), cfg["hidden_size"], cfg["head_dim"]
    dep = cfg["deployment"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n_pub = dep["num_experts_published"]
    if held_pair_share is None:
        held_pair_share = cfg["num_experts"] / n_pub
    shared_width = dep["shared_columns_held"][1]
    ops = []

    def add(site, fwd, act_grads, weight_grad=False):
        ops.append({"site": site, "fwd": float(fwd),
                    "act_operands_with_grad": act_grads,
                    "weight_grad": weight_grad})

    for i, kind in enumerate(cfg["layer_types"]):
        p = f"layers_{i}"
        x = 0 if i == 0 else 1          # does the layer's input carry gradient
        pairs = (banded_pairs(tokens, cfg["sliding_window"])
                 if kind == SLIDING else causal_pairs(tokens))
        add(f"{p}.q_proj", 2 * t * h * hq * hd, x, True)
        add(f"{p}.k_proj", 2 * t * h * hkv * hd, x)
        add(f"{p}.v_proj", 2 * t * h * hkv * hd, x)
        add(f"{p}.attn.qk", 2 * hq * hd * pairs, 1 + x)   # the queries train
        add(f"{p}.attn.pv", 2 * hq * hd * pairs, 1 + x)
        add(f"{p}.o_proj", 2 * t * hq * hd * h, 1)
        add(f"{p}.router", 2 * t * h * n_pub, x)
        rows = t * cfg["num_experts_per_tok"] * held_pair_share
        for name in ("gate_proj", "up_proj", "down_proj"):
            add(f"{p}.experts.{name}",
                2 * rows * h * cfg["intermediate_size"], x)
            add(f"{p}.shared.{name}", 2 * t * h * shared_width, x)
    add("head", 2 * t * h * cfg["vocab_size"], 1)
    return ops


def window_attention_pair_flops(cfg: dict, tokens: int) -> float:
    """Useful operations of the windowed kernel pair over ONE step: every
    sliding layer's forward (2 products) and backward (4) at the banded
    pairs, the query heads held."""
    layers = sum(kind == SLIDING for kind in cfg["layer_types"])
    one_product = (2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
                   * banded_pairs(tokens, cfg["sliding_window"]))
    return layers * (2 + 4) * one_product
