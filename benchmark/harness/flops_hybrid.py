"""Operation counts of the hybrid token model (Granite-4.0-H-Small's block,
one chip's share) from the configuration's shapes.

Counts the WORK, never the implementation (``harness/flops.py``'s
convention): multiply-adds x 2 of every matrix product the architecture
defines for the heads, experts and vocabulary rows HELD here; the
depthwise conv; the state-space scan as its published chunked form's four
products per chunk of ``mamba_chunk_size`` tokens — C B^T and (C B^T ∘ L) X
at the causal pairs inside a chunk, the chunk's state B^T X and the carried
state's C h — attention at the causal pairs (s <= t); routed experts at the
(token, expert) pairs that land on held experts (``held_pair_share``: the
program's counter where given, else the uniform share held / published); no
norms, softmax, activations, decays, top-k, no padding and no recompute.

``hybrid_ops`` returns one record per product (``flops_lm.lm_ops``'s form):
site, fwd, act_operands_with_grad, weight_grad; the tuning step's count is
``flops.tune_step_flops``. Nothing upstream of the first trainable leaf
carries a gradient: layer 0's input is the frozen embedding, so of its mixer
only C (``in_proj_c`` trains) and what C feeds do.
"""

from __future__ import annotations


def hybrid_ops(cfg: dict, tokens: int, held_pair_share: float = None) -> list:
    """One document's forward, product by product. ``cfg``: the
    configuration file (published keys; the counts ``reduced`` names as
    HELD)."""
    t, h = float(tokens), cfg["hidden_size"]
    dep = cfg["deployment"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // dep["num_attention_heads_published"]
    mh, hp, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d, q = mh * hp, min(cfg["mamba_chunk_size"], tokens)
    n_pub = dep["num_local_experts_published"]
    if held_pair_share is None:
        held_pair_share = cfg["num_local_experts"] / n_pub
    chunk_pairs = (tokens // q) * q * (q + 1) / 2.0  # (i, j <= i) in a chunk
    causal_pairs = t * (t + 1) / 2.0
    ops = []

    def add(site, fwd, act_grads, weight_grad=False):
        ops.append({"site": site, "fwd": float(fwd),
                    "act_operands_with_grad": act_grads,
                    "weight_grad": weight_grad})

    for i, kind in enumerate(cfg["layer_types"]):
        p = f"layers_{i}"
        x = 0 if i == 0 else 1          # does the layer's input carry gradient
        if kind == "mamba":
            c = 1                        # C does everywhere: in_proj_c trains
            add(f"{p}.in_proj", 2 * t * h * (2 * d + n + mh), x)
            add(f"{p}.in_proj_c", 2 * t * h * n, x, True)
            add(f"{p}.conv", 2 * t * cfg["mamba_d_conv"] * (d + 2 * n), x)
            add(f"{p}.ssd.cb", 2 * n * chunk_pairs, c + x)
            add(f"{p}.ssd.inside", 2 * mh * hp * chunk_pairs, c + x)
            add(f"{p}.ssd.chunk_state", 2 * t * mh * hp * n, 2 * x)
            add(f"{p}.ssd.carried", 2 * t * mh * hp * n, c + x)
            add(f"{p}.out_proj", 2 * t * d * h, c)
        else:
            qg = 1                       # and the queries: q_proj trains
            add(f"{p}.q_proj", 2 * t * h * hq * hd, x, True)
            add(f"{p}.k_proj", 2 * t * h * hkv * hd, x)
            add(f"{p}.v_proj", 2 * t * h * hkv * hd, x)
            add(f"{p}.attn.qk", 2 * hq * hd * causal_pairs, qg + x)
            add(f"{p}.attn.pv", 2 * hq * hd * causal_pairs, qg + x)
            add(f"{p}.o_proj", 2 * t * hq * hd * h, 1)
        add(f"{p}.router", 2 * t * h * n_pub, 1)
        rows = t * cfg["num_experts_per_tok"] * held_pair_share
        for name in ("gate_proj", "up_proj", "down_proj"):
            add(f"{p}.experts.{name}", 2 * rows * h * cfg["intermediate_size"], 1)
            add(f"{p}.shared.{name}",
                2 * t * h * cfg["shared_intermediate_size"], 1)
    add("head", 2 * t * h * cfg["vocab_size"], 1)
    return ops
