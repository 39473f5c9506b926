"""Operation counts of the token model (DeepSeek-V3.2's block, one chip's
share) from the configuration's shapes.

Counts the WORK, never the implementation (``harness/flops.py``'s
convention): multiply-adds x 2 of every matrix product the architecture
defines for the heads, experts and vocabulary rows HELD here; attention at
the keys SELECTED (sum over queries of min(t + 1, index_topk)), so a
masked-dense implementation reads low; the index scorer's products at the
causal pairs it must score (s <= t), forward only (no gradient flows through
it); routed experts at the (token, expert) pairs that land on held experts
(``held_pair_share`` of tokens x experts a token: the program's counter
where given, else the uniform share held / n_routed); no norms, softmax,
activations, top-k, no padding and no recompute.

``lm_ops`` returns one record per product: site, fwd,
act_operands_with_grad, weight_grad. The tuning step's count is
``flops.tune_step_flops``: forward + one forward-sized product per operand
that carries a gradient + one per trainable weight. Nothing upstream of the
first trainable leaf carries one: layer 0's input is the frozen embedding.
"""

from __future__ import annotations

TRAINABLE = ("q_a_proj", "q_b_proj")


def selected_pairs(tokens: int, topk: int) -> float:
    """Sum over queries t = 0..T-1 of |S_t| = min(t + 1, topk)."""
    k = min(topk, tokens)
    return k * (k + 1) / 2.0 + (tokens - k) * float(k)


def lm_ops(cfg: dict, tokens: int, held_pair_share: float = None,
           trainable=TRAINABLE) -> list:
    """One document's forward, product by product. ``cfg``: the
    configuration file (published keys; ``n_routed_experts`` /
    ``num_attention_heads`` / ``vocab_size`` / depth as HELD)."""
    t, h = float(tokens), cfg["hidden_size"]
    heads, lat_q, lat_kv = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                            cfg["kv_lora_rank"])
    nd, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    n_pub = cfg["deployment"]["n_routed_experts_published"]
    if held_pair_share is None:
        held_pair_share = cfg["n_routed_experts"] / n_pub
    pairs = selected_pairs(tokens, cfg["index_topk"])
    ops = []

    def add(site, fwd, act_grads, weight_grad=False):
        ops.append({"site": site, "fwd": float(fwd),
                    "act_operands_with_grad": act_grads,
                    "weight_grad": weight_grad})

    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        x = 0 if i == 0 else 1          # does the layer's input carry gradient
        tr_a, tr_b = "q_a_proj" in trainable, "q_b_proj" in trainable
        q = 1 if (x or tr_a) else 0     # does c_q
        add(f"{p}.q_a_proj", 2 * t * h * lat_q, x, tr_a)
        add(f"{p}.q_b_proj", 2 * t * lat_q * heads * (nd + rd), q, tr_b)
        add(f"{p}.kv_a_proj", 2 * t * h * (lat_kv + rd), x)
        add(f"{p}.kv_b_proj", 2 * t * lat_kv * heads * (nd + vd), x)
        add(f"{p}.indexer.wq_b", 2 * t * lat_q * cfg["index_n_heads"]
            * cfg["index_head_dim"], 0)
        add(f"{p}.indexer.wk", 2 * t * h * cfg["index_head_dim"], 0)
        add(f"{p}.indexer.weights_proj", 2 * t * h * cfg["index_n_heads"], 0)
        add(f"{p}.indexer.scores", 2 * cfg["index_n_heads"]
            * cfg["index_head_dim"] * t * (t + 1) / 2, 0)
        add(f"{p}.attn.qk", 2 * heads * (nd + rd) * pairs, 1 + x)
        add(f"{p}.attn.pv", 2 * heads * vd * pairs, 1 + x)
        add(f"{p}.o_proj", 2 * t * heads * vd * h, 1)
        if i < cfg["first_k_dense_replace"]:
            for n in ("gate_proj", "up_proj", "down_proj"):
                add(f"{p}.mlp.{n}", 2 * t * h * cfg["intermediate_size"], 1)
            continue
        add(f"{p}.router", 2 * t * h * n_pub, 1)
        width = cfg["moe_intermediate_size"]
        rows = t * cfg["num_experts_per_tok"] * held_pair_share
        for n in ("gate_proj", "up_proj", "down_proj"):
            add(f"{p}.experts.{n}", 2 * rows * h * width, 1)
            add(f"{p}.shared.{n}", 2 * t * h * width * cfg["n_shared_experts"], 1)
    add("head", 2 * t * h * cfg["vocab_size"], 1)
    return ops
