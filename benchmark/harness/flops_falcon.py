"""Operation counts of the fifth token family (Falcon-H1-34B's parallel
hybrid block, whole layers on one chip) from the configuration's shapes.

Counts the WORK, never the implementation (``harness/flops.py``'s
convention): multiply-adds x 2 of every matrix product the architecture
defines for the layers and vocabulary rows HELD here; the depthwise conv;
the state-space scan as its published chunked form's four products per
chunk of ``mamba_chunk_size`` tokens — C B^T per group and (C B^T ∘ L) X per
head at the causal pairs inside a chunk, the chunk's state B^T X and the
carried state's C h per head — attention at the causal pairs (s <= t); the
dense feed-forward; no norms, softmax, rotary, activations, decays,
multipliers, no padding and no recompute.

``falcon_ops`` returns one record per product (``flops_lm.lm_ops``'s form):
site, fwd, act_operands_with_grad, weight_grad; the tuning step's count is
``flops.tune_step_flops``. Layer 0's input is the frozen embedding, so of
its mixer only C (``in_proj_c`` trains) and what C feeds carry a gradient,
of its attention only ``q_proj`` and what the queries feed; its
feed-forward reads the residual after both branches, which does.
"""

from __future__ import annotations


def falcon_ops(cfg: dict, tokens: int) -> list:
    """One document's forward, product by product. ``cfg``: the
    configuration file (published keys; the counts ``reduced`` names as
    HELD)."""
    t, h = float(tokens), cfg["hidden_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    mh, hp, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    groups, d = cfg["mamba_n_groups"], cfg["mamba_d_ssm"]
    q = min(cfg["mamba_chunk_size"], tokens)
    chunk_pairs = (tokens // q) * q * (q + 1) / 2.0  # (i, j <= i) in a chunk
    causal_pairs = t * (t + 1) / 2.0
    ops = []

    def add(site, fwd, act_grads, weight_grad=False):
        ops.append({"site": site, "fwd": float(fwd),
                    "act_operands_with_grad": act_grads,
                    "weight_grad": weight_grad})

    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        x = 0 if i == 0 else 1          # does the layer's input carry gradient
        # the mixer: C carries gradient everywhere (in_proj_c trains)
        add(f"{p}.in_proj", 2 * t * h * (2 * d + groups * n + mh), x)
        add(f"{p}.in_proj_c", 2 * t * h * groups * n, x, True)
        add(f"{p}.conv", 2 * t * cfg["mamba_d_conv"] * (d + 2 * groups * n), x)
        add(f"{p}.ssd.cb", 2 * groups * n * chunk_pairs, 1 + x)
        add(f"{p}.ssd.inside", 2 * mh * hp * chunk_pairs, 1 + x)
        add(f"{p}.ssd.chunk_state", 2 * t * mh * hp * n, 2 * x)
        add(f"{p}.ssd.carried", 2 * t * mh * hp * n, 1 + x)
        add(f"{p}.out_proj", 2 * t * d * h, 1)
        # attention: the queries carry gradient everywhere (q_proj trains)
        add(f"{p}.q_proj", 2 * t * h * hq * hd, x, True)
        add(f"{p}.k_proj", 2 * t * h * hkv * hd, x)
        add(f"{p}.v_proj", 2 * t * h * hkv * hd, x)
        add(f"{p}.attn.qk", 2 * hq * hd * causal_pairs, 1 + x)
        add(f"{p}.attn.pv", 2 * hq * hd * causal_pairs, 1 + x)
        add(f"{p}.o_proj", 2 * t * hq * hd * h, 1)
        for name in ("gate_proj", "up_proj", "down_proj"):
            add(f"{p}.mlp.{name}", 2 * t * h * cfg["intermediate_size"], 1)
    add("head", 2 * t * h * cfg["vocab_size"], 1)
    return ops
