"""Operation counts of the fourth token family (Keye-VL-2.0's language
model, whole layers on one chip) from the configuration's shapes, and the
selection pair's.

Counts the WORK, never the implementation (``harness/flops.py``'s
convention): multiply-adds x 2 of every matrix product the architecture
defines for the layers and vocabulary rows HELD here; attention at the keys
SELECTED (the program's ``keys_selected_mean`` where given, else the sum
over queries of min(t + 1, topk)), so a pair that walks every causal tile
reads low; the index scorer's products at the causal pairs it must score
(s <= t), forward only (no gradient flows through it); the experts at the
(token, expert) pairs that land on held experts (``held_pair_share``: the
program's counter where given, else held / published, 1 here); no norms,
softmax, rotary, activations, top-k, no padding, no masked tile and no
recompute.

``keye_ops`` returns one record per product (``flops_lm.lm_ops``'s form):
site, fwd, act_operands_with_grad, weight_grad; the tuning step's count is
``flops.tune_step_flops``. Layer 0's input is the frozen embedding, so of
layer 0's attention only ``q_proj`` and what the queries feed carry a
gradient; its expert layer reads x after the attention, which does.

``selected_attention_pair_flops`` is the roofline's numerator: every
layer's two attention products at the selected pairs as the kernel pair has
to do them — 2 forward (Q K^T, P V) + 4 backward (dV, dP, dQ, dK; the
scores' recompute inside the backward kernel is no useful work), over the
query heads, once a layer and step however often a layer's recompute runs
the forward kernel.
"""

from __future__ import annotations

from benchmark.harness.flops_lm import selected_pairs


def _pairs(cfg: dict, tokens: int, keys_selected_mean=None) -> float:
    if keys_selected_mean is not None:
        return float(keys_selected_mean) * tokens
    return selected_pairs(tokens, cfg["sa_config"]["topk"])


def keye_ops(cfg: dict, tokens: int, held_pair_share: float = None,
             keys_selected_mean: float = None) -> list:
    """One document's forward, product by product. ``cfg``: the
    configuration file (published keys; the counts ``reduced`` names as
    HELD)."""
    t, h, hd = float(tokens), cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa, n = cfg["sa_config"], cfg["num_experts"]
    if held_pair_share is None:
        held_pair_share = (cfg["deployment"]["experts_held"][1]
                           / cfg["deployment"]["num_experts_published"])
    pairs = _pairs(cfg, tokens, keys_selected_mean)
    ops = []

    def add(site, fwd, act_grads, weight_grad=False):
        ops.append({"site": site, "fwd": float(fwd),
                    "act_operands_with_grad": act_grads,
                    "weight_grad": weight_grad})

    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        x = 0 if i == 0 else 1          # does the layer's input carry gradient
        add(f"{p}.q_proj", 2 * t * h * hq * hd, x, True)
        add(f"{p}.k_proj", 2 * t * h * hkv * hd, x)
        add(f"{p}.v_proj", 2 * t * h * hkv * hd, x)
        ih, inh = sa["indexer_head_dim"], sa["indexer_num_heads"]
        add(f"{p}.indexer.wq_b", 2 * t * h * inh * ih, 0)
        add(f"{p}.indexer.wk", 2 * t * h * ih, 0)
        add(f"{p}.indexer.weights_proj", 2 * t * h * inh, 0)
        add(f"{p}.indexer.scores", 2 * inh * ih * t * (t + 1) / 2, 0)
        add(f"{p}.attn.qk", 2 * hq * hd * pairs, 1 + x)   # the queries train
        add(f"{p}.attn.pv", 2 * hq * hd * pairs, 1 + x)
        add(f"{p}.o_proj", 2 * t * hq * hd * h, 1)
        add(f"{p}.router", 2 * t * h * n, 1)
        rows = t * cfg["num_experts_per_tok"] * held_pair_share
        for name in ("gate_proj", "up_proj", "down_proj"):
            add(f"{p}.experts.{name}",
                2 * rows * h * cfg["moe_intermediate_size"], 1)
    add("head", 2 * t * h * cfg["vocab_size"], 1)
    return ops


def selected_attention_pair_flops(cfg: dict, tokens: int,
                                  keys_selected_mean: float = None) -> float:
    """Useful operations of the selection pair over ONE step: every layer's
    forward (2 products) and backward (4) at the selected pairs, all the
    query heads."""
    one_product = (2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
                   * _pairs(cfg, tokens, keys_selected_mean))
    return cfg["num_hidden_layers"] * (2 + 4) * one_product
