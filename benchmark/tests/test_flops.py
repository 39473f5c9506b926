"""The flop counter against hand counts: one resnet block, one transformer
block, and the whole tiny preset."""

import json
import os

from benchmark.harness import flops

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {"block_out_channels": [8, 16], "layers_per_block": 1,
        "down_block_types": ["CrossAttnDownBlock3D", "DownBlock3D"],
        "up_block_types": ["UpBlock3D", "CrossAttnUpBlock3D"],
        "cross_attention_dim": 16, "in_channels": 4, "out_channels": 4}


def _sd15(name="sd15-tune-8f"):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def hand_resnet(f, r, cin, cout, temb):
    n = f * r * r
    total = 2 * n * 9 * cin * cout + 2 * temb * cout + 2 * n * 9 * cout * cout
    if cin != cout:
        total += 2 * n * cin * cout
    return total


def hand_transformer(f, r, c, L, D):
    n = r * r
    dense = 2 * f * n * c * c
    frame = dense + 2 * (2 * n * c * c) + 2 * (2 * f * n * n * c) + dense
    cross = dense + 2 * (2 * L * D * c) + 2 * (2 * f * n * L * c) + dense
    ff = 2 * f * n * c * 8 * c + 2 * f * n * 4 * c * c
    temp = 3 * dense + 2 * (2 * n * f * f * c) + dense
    return dense + frame + cross + ff + temp + dense  # proj_in ... proj_out


def test_resnet_block_hand_count():
    w = flops._Walk(8, flops.TRAINABLE)
    flops._resnet(w, "r", 320, 640, 32, 1280)
    assert sum(o["fwd"] for o in w.ops) == hand_resnet(8, 32, 320, 640, 1280)
    w = flops._Walk(8, flops.TRAINABLE)
    flops._resnet(w, "r", 640, 640, 32, 1280)
    assert sum(o["fwd"] for o in w.ops) == hand_resnet(8, 32, 640, 640, 1280)


def test_transformer_block_hand_count():
    w = flops._Walk(8, flops.TRAINABLE)
    flops._transformer(w, "t", 320, 64, 77, 768)
    assert sum(o["fwd"] for o in w.ops) == hand_transformer(8, 64, 320, 77, 768)
    # the frame attention's two products are 4 F N N C (= 4 B F H N N D)
    qkpv = sum(o["fwd"] for o in w.ops if ".attn1.qk" in o["site"]
               or ".attn1.pv" in o["site"])
    assert qkpv == 4 * 8 * 4096 * 4096 * 320


def test_whole_tiny_preset_hand_count():
    f, L, D, temb = 2, 77, 16, 32
    ops = flops.unet_ops(TINY, f, 8, L)
    hand = 2 * 8 * temb + 2 * temb * temb            # time embedding
    hand += 2 * f * 64 * 9 * 4 * 8                   # conv_in
    hand += hand_resnet(f, 8, 8, 8, temb) + hand_transformer(f, 8, 8, L, D)
    hand += 2 * f * 16 * 9 * 8 * 8                   # downsample to 4x4
    hand += hand_resnet(f, 4, 8, 16, temb)           # DownBlock3D
    hand += 2 * hand_resnet(f, 4, 16, 16, temb) \
        + hand_transformer(f, 4, 16, L, D)           # mid
    hand += hand_resnet(f, 4, 32, 16, temb) + hand_resnet(f, 4, 24, 16, temb)
    hand += 2 * f * 64 * 9 * 16 * 16                 # upsample to 8x8
    hand += hand_resnet(f, 8, 24, 8, temb) + hand_transformer(f, 8, 8, L, D)
    hand += hand_resnet(f, 8, 16, 8, temb) + hand_transformer(f, 8, 8, L, D)
    hand += 2 * f * 64 * 9 * 8 * 4                   # conv_out
    assert flops.forward_flops(ops) == hand


def test_tune_count_convention():
    ops = {o["site"]: o for o in flops.unet_ops(_sd15(), 8, 64, 77)}
    first = "down_blocks_0.attentions_0.blocks_0"
    # nothing upstream of the first trainable leaf carries a gradient
    assert ops["conv_in"]["act_operands_with_grad"] == 0
    assert ops["down_blocks_0.resnets_0.conv1"]["act_operands_with_grad"] == 0
    assert ops[first + ".attn1.to_q"] == {
        "site": first + ".attn1.to_q", "fwd": 2.0 * 8 * 4096 * 320 * 320,
        "act_operands_with_grad": 0, "weight_grad": True}
    assert ops[first + ".attn1.qk"]["act_operands_with_grad"] == 1
    # the text side never carries one; downstream everything does
    assert ops[first + ".attn2.to_k"]["act_operands_with_grad"] == 0
    assert ops[first + ".attn2.qk"]["act_operands_with_grad"] == 1
    assert ops[first + ".ff.proj_out"]["act_operands_with_grad"] == 1
    assert not ops[first + ".ff.proj_out"]["weight_grad"]
    assert ops[first + ".attn_temp.to_k"]["weight_grad"]
    second = "down_blocks_0.attentions_1.blocks_0"
    assert ops[second + ".attn1.qk"]["act_operands_with_grad"] == 2
    assert ops["mid_block.resnets_0.time_emb_proj"][
        "act_operands_with_grad"] == 0
    fwd = flops.forward_flops(list(ops.values()))
    tune = flops.tune_step_flops(list(ops.values()))
    assert 2.0 * fwd < tune < 3.0 * fwd


def test_sd15_counts_and_sites():
    full = flops.unet_ops(_sd15("sd15-tune-8f"), 8, 64, 77)
    cut = flops.unet_ops(_sd15("sd15-edit-8f"), 8, 64, 77)
    # 0.83 TFLOP a frame at full depth (the old bench.py constant said 0.82)
    assert abs(flops.forward_flops(full) / 8 - 0.83e12) < 0.01e12
    assert flops.forward_flops(cut) < flops.forward_flops(full)
    assert flops.attention_sites(_sd15("sd15-tune-8f"), 64) == (
        [(64, 320)] * 2 + [(32, 640)] * 2 + [(16, 1280)] * 2 + [(8, 1280)]
        + [(16, 1280)] * 3 + [(32, 640)] * 3 + [(64, 320)] * 3)
    assert len(flops.attention_sites(_sd15("sd15-edit-8f"), 64)) == 10
