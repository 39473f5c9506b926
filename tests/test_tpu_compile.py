"""The main path's Pallas kernels, compiled for a described TPU v5e — no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (``v5e:2x2``): it refuses what the chip's compiler
would refuse — a slice off the tiling, a kernel over its VMEM budget, a
kernel that cannot be partitioned — which Pallas interpret mode on the CPU
never shows. Each case compiles one kernel (or, for Stage-1 tuning, the
forward / backward pair through ``jax.grad``) at an SD-1.5 shape — or the
token models' attention pair at their cells' shapes, with its selection
operand and without — bare or
under ``jax.shard_map`` on a four-device mesh with the specs
``parallel/mesh.py`` uses, and asserts the kernel is IN the compiled text
(``tpu_custom_call``). A compile that passes is not a run: nothing executes,
no result and no time comes out of this file.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture that skips when it cannot be —
never at import or collection time, never in ``conftest.py`` — because only
one process may load the TPU library and every xdist worker imports every
test file; all cases stay in THIS one file so one worker holds the library;
the persistent compile cache is off around the compiles (an entry compiled
for a described chip cannot be read back without one).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from videop2p_tpu.models import deepseek as ds
from videop2p_tpu.obs.introspect import tpu_custom_call_counts
from videop2p_tpu.ops.attention import fused_bwd_block, fused_frame_attention
from videop2p_tpu.ops.grouped_experts import grouped_expert_tiles
from videop2p_tpu.ops.groupnorm import fits_fused_group_norm, fused_group_norm
from videop2p_tpu.ops.selected_attention import (
    causal_attention,
    grouped_selected_attention,
    keep_attention_outputs,
    selected_attention_tiles,
    selected_key_attention,
)
from videop2p_tpu.parallel.mesh import AXIS_DATA, AXIS_FRAMES, AXIS_TENSOR

# (B, F, H, N, D) of the SD-1.5 frame-attention sites that pass the
# min_large_tokens=1024 gate: 64² and 32² latents at the 2-stream cached
# edit batch, and the 24-frame long-video shape
ATTENTION_SHAPES = [(2, 8, 8, 4096, 40), (2, 8, 8, 1024, 80),
                    (2, 24, 8, 4096, 40)]
# the same sites as Stage-1 tuning differentiates through them (batch 1),
# and a 24-frame clip
TUNE_ATTENTION_SHAPES = [(1, 8, 8, 4096, 40), (1, 8, 8, 1024, 80),
                         (1, 24, 8, 4096, 40)]
# tokens of the token model's selected-key attention (8 heads of 128 / 64 /
# 128, models/deepseek.py): the cell's document, and a quarter of it, where
# the backward's resident dQ lets all eight heads into one cell
SELECTED_TOKENS = [16384, 4096]
# tokens of the hybrid token model's attention layer (8 query heads on 2 key /
# value heads of 128, models/granite_hybrid.py): the same pair with no
# selection operand, at the cell's document and at an eighth of it
CAUSAL_TOKENS = [32768, 4096]
# the token cells' expert layers as one chip holds them — tokens, hidden,
# held of all experts, inner width, experts a token: the hybrid cell's (an
# expert's three matrices stay in VMEM) and the other's (tiles of the 2048)
EXPERT_LAYERS = {"granite_18x768": (32768, 4096, (18, 72), 768, 10),
                 "deepseek_16x2048": (16384, 7168, (16, 256), 2048, 8),
                 # the third family's: 4096 x 4096 experts in inner tiles of
                 # 1024, 16 held of 128, beside 2048 held columns of its four
                 # shared experts
                 "command_16x4096": (32768, 4096, (16, 128), 4096, 8, 2048),
                 # the fourth family's: a WHOLE layer, all 128 experts of
                 # 768, and no shared expert
                 "keye_128x768": (16384, 2048, (128, 128), 768, 8, 0)}
# the third family's sliding layers (models/cohere2_moe.py): 16 query heads on
# ONE key / value head of 128, a window of 4096 keys, at the cell's document
# and at an eighth of it (one window: the causal walk)
WINDOW_TOKENS = [32768, 4096]
# (rows, C) slab classes of the SD-1.5 GroupNorm sites the kernel covers
GROUP_NORM_SLABS = [(4096, 320), (1024, 640), (256, 1280), (512, 1280)]


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """``--mesh 1,4,1`` on the described devices: frames over four chips."""
    import numpy as np

    return Mesh(np.asarray(topo.devices).reshape(1, 4, 1),
                (AXIS_DATA, AXIS_FRAMES, AXIS_TENSOR))


def _kernels(fn, *shapes):
    """Compile ``fn`` at abstract ``shapes``; the kernels in its text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return tpu_custom_call_counts(text)


def _attention_shapes(shape, q_sharding, kv_sharding):
    b, f, h, n, d = shape
    q = jax.ShapeDtypeStruct((b, f, h, n, d), jnp.bfloat16, sharding=q_sharding)
    kv = jax.ShapeDtypeStruct((b, h, n, d), jnp.bfloat16, sharding=kv_sharding)
    return q, kv, kv


@pytest.mark.parametrize("shape", ATTENTION_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_frame_attention_compiles(one_chip, shape):
    kernels = _kernels(
        lambda q, k, v: fused_frame_attention(q, k, v, 256),
        *_attention_shapes(shape, one_chip, one_chip),
    )
    assert kernels == {"fused_frame_attention": 1}


def _attention_grad(fn):
    """dQ, dK, dV through ``fn``: the forward kernel and the backward one."""
    return jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))


@pytest.mark.parametrize("shape", TUNE_ATTENTION_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_frame_attention_grad_compiles(one_chip, shape):
    """Forward + backward at the tune's shapes: the backward's VMEM budget
    (``ops.attention._fused_bwd_vmem_bytes``, handed to the compiler as the
    kernel's limit) is one the chip's compiler accepts."""
    b, f, h, n, d = shape
    assert fused_bwd_block(f * n, n, d, jnp.bfloat16) is not None
    kernels = _kernels(
        _attention_grad(lambda q, k, v: fused_frame_attention(q, k, v, 256)),
        *_attention_shapes(shape, one_chip, one_chip),
    )
    assert kernels == {"fused_frame_attention": 1,
                       "fused_frame_attention_bwd": 1}


def _selected_attention_shapes(t_len, sharding, heads=8, nope=128, rope=64,
                               v_dim=128):
    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (arg((t_len, heads, nope)), arg((t_len, heads, rope)),
            arg((t_len, heads, nope)), arg((t_len, rope)),
            arg((t_len, heads, v_dim)), arg((t_len, t_len), jnp.bool_))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("t_len", SELECTED_TOKENS)
def test_selected_key_attention_compiles(one_chip, t_len, grad):
    """The token model's pair at the cell's shape: the forward alone, and the
    forward + backward through ``jax.grad`` of all five operands — the VMEM
    the fit test counts (``ops.selected_attention._bwd_vmem_bytes``: the
    resident dQ of two heads at 16384 tokens, of all eight at 4096) is one
    the chip's compiler accepts."""
    assert selected_attention_tiles(t_len, 8, 128, 64, 128, jnp.bfloat16) is not None

    def fn(*ops):
        return selected_key_attention(*ops, 192 ** -0.5)

    if grad:
        kernels = _kernels(
            jax.grad(lambda *ops: jnp.sum(fn(*ops).astype(jnp.float32) ** 2),
                     argnums=(0, 1, 2, 3, 4)),
            *_selected_attention_shapes(t_len, one_chip))
        assert kernels == {"lm_selected_attention": 1,
                           "lm_selected_attention_bwd": 1}
    else:
        kernels = _kernels(fn, *_selected_attention_shapes(t_len, one_chip))
        assert kernels == {"lm_selected_attention": 1}


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("t_len", CAUSAL_TOKENS)
def test_causal_attention_compiles(one_chip, t_len, grad):
    """The pair WITHOUT its selection operand (the causal rule from the
    tile's position) at the hybrid cell's shape — 32768 tokens, where the
    backward's resident dQ lets two heads into a cell — forward alone and
    through ``jax.grad`` of q, k and v."""
    assert selected_attention_tiles(t_len, 8, 128, 0, 128, jnp.bfloat16) is not None

    def arg(heads):
        return jax.ShapeDtypeStruct((t_len, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def fn(q, k, v):
        return causal_attention(q, k, v, 0.0078125)

    if grad:
        kernels = _kernels(
            jax.grad(lambda *ops: jnp.sum(fn(*ops).astype(jnp.float32) ** 2),
                     argnums=(0, 1, 2)), arg(8), arg(2), arg(2))
        assert kernels == {"lm_selected_attention": 1,
                           "lm_selected_attention_bwd": 1}
    else:
        assert _kernels(fn, arg(8), arg(2), arg(2)) == {
            "lm_selected_attention": 1}


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("t_len", WINDOW_TOKENS)
def test_windowed_attention_compiles(one_chip, t_len, grad):
    """The pair with a WINDOW (the band's step tables, the band's in-tile
    rule) at the third family's shape — 16 query heads on one key / value
    head — forward alone and through ``jax.grad`` of q, k and v; the kernels
    lie under ``lm.window_attention``, what ``window_attention_ms.tune`` and
    the roofline read, and not under the full layers' ``lm.attention``."""
    assert selected_attention_tiles(t_len, 16, 128, 0, 128, jnp.bfloat16) is not None

    def arg(heads):
        return jax.ShapeDtypeStruct((t_len, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def fn(q, k, v):
        return causal_attention(q, k, v, 128 ** -0.5, window=4096)

    want = {"lm_selected_attention": 1}
    if grad:
        want["lm_selected_attention_bwd"] = 1
        fn = jax.grad(lambda *ops, fn=fn: jnp.sum(
            fn(*ops).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(arg(16), arg(1), arg(1)).compile().as_text()
    assert tpu_custom_call_counts(text) == want
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split('op_name="')[1].split('"')[0]
            assert "lm.window_attention" in name and "lm.attention" not in name


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_grouped_selected_attention_compiles(one_chip, grad):
    """The pair WITH its selection operand over grouped-query heads at the
    fourth family's cell — 16384 tokens, 32 query heads of 128 on 4 key /
    value heads (8 heads a forward cell, 4 a backward cell) — forward alone
    and through ``jax.grad`` of q, k and v; the kernels lie under
    ``lm.sparse_attention``, what ``sparse_attention_ms.tune`` and the
    roofline read."""
    t_len = 16384
    assert selected_attention_tiles(t_len, 32, 128, 0, 128, jnp.bfloat16) is not None

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, mask):
        return grouped_selected_attention(q, k, v, mask, 128 ** -0.5)

    want = {"lm_selected_attention": 1}
    if grad:
        want["lm_selected_attention_bwd"] = 1
        fn = jax.grad(lambda *ops, fn=fn: jnp.sum(
            fn(*ops).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(arg((t_len, 32, 128)), arg((t_len, 4, 128)),
                             arg((t_len, 4, 128)),
                             arg((t_len, t_len), jnp.bool_)).compile().as_text()
    assert tpu_custom_call_counts(text) == want
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            assert "lm.sparse_attention" in line.split('op_name="')[1].split('"')[0]


def _family_attention(family):
    """``(module, cfg, tokens, layer)`` of a token family at its cell: the
    configuration the tuner's YAML states, and in ``layer(p, x, *given)`` the
    family's own ``attention`` on its residual — a sliding layer where the
    family has them and ``family`` says so."""
    from videop2p_tpu.cli.common import _token_families, load_config

    name, _, kind = family.partition(":")
    tune = load_config({"deepseek_v32": "configs/deepseek-v32-s16-tune.yaml",
                        "granitemoehybrid": "configs/granite-4.0-h-small-s4-tune.yaml",
                        "cohere2_moe": "configs/command-a-plus-s8-tune.yaml",
                        "keye_vl2": "configs/keye-vl2-30b-a3b-s1-tune.yaml",
                        "falcon_h1": "configs/falcon-h1-34b-s1-tune.yaml"}[name])
    module, config_cls = _token_families()[name]
    cfg = config_cls.from_dict(tune["model"])
    t_len = tune["train_data"]["n_tokens"]
    if name == "deepseek_v32":
        def layer(p, x, mask):
            return x + module.attention(
                p, cfg, x, module.rope_angles(cfg, jnp.arange(t_len)), mask)[0]
    elif name == "granitemoehybrid":
        def layer(p, x):
            return x + module.attention(p, cfg, x)
    elif name == "keye_vl2":
        def layer(p, x, mask):
            return x + module.attention(
                p, cfg, x, module.rope_angles(cfg, jnp.arange(t_len)), mask)
    elif name == "falcon_h1":
        def layer(p, x):
            return x + module.attention(
                p, cfg, x, module.rope_angles(cfg, jnp.arange(t_len)))
    else:
        def layer(p, x):
            angles = (module.rope_angles(cfg, jnp.arange(t_len))
                      if kind == "sliding" else None)
            return x + module.attention(p, cfg, x, angles)[0]
    return module, cfg, t_len, layer


@pytest.mark.parametrize("family", ["deepseek_v32", "granitemoehybrid",
                                    "cohere2_moe:sliding", "cohere2_moe:full",
                                    "keye_vl2", "falcon_h1"])
def test_a_layers_recompute_keeps_the_attention_pairs_outputs(one_chip,
                                                              monkeypatch,
                                                              family):
    """Each token family's ``attention`` at its cell's shape, as the model
    dispatches it on the chip, differentiated through the checkpoint its
    ``_forward`` wraps a layer in: with ``keep_attention_outputs`` the
    compiled gradient holds the forward kernel ONCE a layer (``oᵀ`` and the
    log-sum-exp are kept across the recompute), under a bare
    ``jax.checkpoint`` twice — so this fails if the names ever slip outside
    the forward rule, or a family's layer stops carrying them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    module, cfg, t_len, layer = _family_attention(family)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    attn = next(layer_p["attn"] for layer_p in
                module.param_shapes(cfg)["params"].values() if "attn" in layer_p)
    args = [jax.tree.map(lambda spec: arg(spec[0]), attn,
                         is_leaf=ds._is_spec),
            arg((t_len, cfg.hidden_size))]
    if family in ("deepseek_v32", "keye_vl2"):
        args.append(arg((t_len, t_len), jnp.bool_))

    def kernels(policy):
        wrapped = jax.checkpoint(layer, policy=policy)
        return _kernels(jax.grad(
            lambda *a: jnp.sum(wrapped(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1)), *args)

    assert kernels(keep_attention_outputs) == {
        "lm_selected_attention": 1, "lm_selected_attention_bwd": 1}
    assert kernels(None) == {
        "lm_selected_attention": 2, "lm_selected_attention_bwd": 1}


@pytest.mark.parametrize("shape", TUNE_ATTENTION_SHAPES[:2],
                         ids=lambda s: "x".join(map(str, s)))
def test_a_blocks_recompute_keeps_the_frame_attention_output(one_chip,
                                                             monkeypatch,
                                                             shape):
    """A UNet transformer site at the tune's 64² and 32² shapes, as the model
    dispatches it on the chip, differentiated through the remat the UNet
    wraps its blocks in: with ``remat_block`` (the policy
    ``keep_frame_attention_output``) the compiled gradient holds the forward
    frame-attention kernel ONCE, under a bare ``nn.remat`` twice — so this
    fails if the name ever slips off the value the recompute needs."""
    from flax import linen as nn

    from videop2p_tpu.models import Transformer3DModel
    from videop2p_tpu.models.unet_blocks import remat_block
    from videop2p_tpu.ops.attention import make_frame_attention_fn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, f, heads, n, d = shape
    side = int(n ** 0.5)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, context = arg((b, f, side, side, heads * d)), arg((b, 77, 768))

    def kernels(wrap):
        model = wrap(Transformer3DModel)(
            heads=heads, dim_head=d, dtype=jnp.bfloat16,
            frame_attention_fn=make_frame_attention_fn("auto"))
        params = jax.tree.map(
            lambda s: arg(s.shape, s.dtype),
            jax.eval_shape(model.init, jax.random.key(0), x, context))
        counts = _kernels(jax.grad(
            lambda p, x, c: jnp.sum(model.apply(p, x, c).astype(jnp.float32) ** 2),
            argnums=(0, 1)), params, x, context)
        return {k: v for k, v in counts.items()
                if k.startswith("fused_frame_attention")}

    assert kernels(remat_block) == {
        "fused_frame_attention": 1, "fused_frame_attention_bwd": 1}
    assert kernels(nn.remat) == {
        "fused_frame_attention": 2, "fused_frame_attention_bwd": 1}


@pytest.mark.parametrize("act", ["none", "silu"])
@pytest.mark.parametrize("slab", GROUP_NORM_SLABS,
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_group_norm_compiles(one_chip, slab, act):
    rows, c = slab
    assert fits_fused_group_norm(rows, c, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((16, rows, c), jnp.bfloat16, sharding=one_chip)
    sb = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
    kernels = _kernels(
        functools.partial(fused_group_norm, num_groups=32, eps=1e-5, act=act),
        x, sb, sb,
    )
    assert kernels == {"fused_group_norm": 1}


def _expert_layer(one_chip, t_len, hidden, held, inner, k, shared=128):
    """``held_expert_ffn`` GIVEN a routing, as every token family calls it,
    and its abstract arguments: the held experts' stacked matrices, a shared
    expert (``shared``: the columns held; 0, none), ``x``, the experts a
    token and their gates."""
    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def swiglu(lead, width):
        return {"gate_proj": {"kernel": arg(lead + (hidden, width))},
                "up_proj": {"kernel": arg(lead + (hidden, width))},
                "down_proj": {"kernel": arg(lead + (width, hidden))}}

    p = {"experts": swiglu((held[0],), inner)}
    if shared:
        p["shared"] = swiglu((), shared)

    def routed(p, x, experts, gates):
        return ds.held_expert_ffn(p, x, experts, gates, (0, held[0]))[0]

    return routed, (p, arg((t_len, hidden)), arg((t_len, k), jnp.int32),
                    arg((t_len, k), jnp.float32))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("layer", EXPERT_LAYERS)
def test_grouped_experts_compile(one_chip, monkeypatch, layer, grad):
    """The grouped expert loop through ``held_expert_ffn`` at both token
    cells' shapes, as the model dispatches it on the chip (the backend is
    what the test says; the shapes pass the fit test): the forward alone, and
    forward + backward through ``jax.grad`` in ``x`` and the gates. The VMEM
    the fit test counts is one the chip's compiler accepts, both kernels are
    in the text, and their ``op_name`` lies under the scope ``lm.experts`` —
    what ``experts_ms.tune`` reads — while the table's sort does not."""
    t_len, hidden, held, inner, k = EXPERT_LAYERS[layer][:5]
    tiles = grouped_expert_tiles(t_len * k, hidden, inner, ds.EXPERT_BLOCK,
                                 jnp.bfloat16)
    assert tiles is not None and inner % tiles.inner == 0
    assert max(tiles.fwd_vmem, tiles.bwd_vmem) <= 100 * 2 ** 20  # of 128 MiB
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    routed, args = _expert_layer(one_chip, *EXPERT_LAYERS[layer])
    fn = routed
    if grad:
        fn = jax.grad(lambda *a: jnp.sum(routed(*a).astype(jnp.float32) ** 2),
                      argnums=(1, 3))
    text = jax.jit(fn).lower(*args).compile().as_text()
    want = {"lm_grouped_experts": 1}
    if grad:
        want["lm_grouped_experts_bwd"] = 1
    assert tpu_custom_call_counts(text) == want
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            assert "lm.experts" in line.split('op_name="')[1].split('"')[0], line
        if " sort(" in line and "op_name=" in line:
            assert "lm.router" in line.split('op_name="')[1].split('"')[0], line


def test_an_expert_layer_off_the_tiling_compiles_as_the_xla_loop(one_chip,
                                                                 monkeypatch):
    """33 lane tiles a row is no whole number of (8, 128) tiles: on the chip
    too the layer is the XLA loop, forward and backward, with no kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert grouped_expert_tiles(2048 * 8, 4224, 256, ds.EXPERT_BLOCK,
                                jnp.bfloat16) is None
    routed, args = _expert_layer(one_chip, 2048, 4224, (4, 16), 256, 8)
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(routed(*a).astype(jnp.float32) ** 2),
        argnums=(1, 3))).lower(*args).compile().as_text()
    assert tpu_custom_call_counts(text) == {}
    assert " while(" in text


def _sharded_attention(mesh4, shape):
    """The kernel under ``jax.shard_map`` with the specs of
    ``parallel.mesh.make_sharded_frame_attention_fn`` — queries over
    ``frames``, the frame-0 K/V replicated across it — and its abstract
    arguments at ``shape``."""
    qspec = P(AXIS_DATA, AXIS_FRAMES, AXIS_TENSOR, None, None)
    kvspec = P(AXIS_DATA, AXIS_TENSOR, None, None)
    fn = jax.shard_map(
        lambda q, k, v: fused_frame_attention(q, k, v, 256), mesh=mesh4,
        in_specs=(qspec, kvspec, kvspec), out_specs=qspec, check_vma=False,
    )
    return fn, _attention_shapes(
        shape, NamedSharding(mesh4, qspec), NamedSharding(mesh4, kvspec))


@pytest.mark.parametrize("shape", ATTENTION_SHAPES[:2],
                         ids=lambda s: "x".join(map(str, s)))
def test_sharded_frame_attention_compiles(mesh4, shape):
    fn, args = _sharded_attention(mesh4, shape)
    assert _kernels(fn, *args) == {"fused_frame_attention": 1}


def test_sharded_frame_attention_grad_compiles(mesh4):
    """The kernel pair under the same ``shard_map``: each chip differentiates
    its two frames, and the replicated K / V get their cotangents summed over
    ``frames`` by the transpose (an all-reduce)."""
    fn, args = _sharded_attention(mesh4, TUNE_ATTENTION_SHAPES[0])
    text = jax.jit(_attention_grad(fn)).lower(*args).compile().as_text()
    assert tpu_custom_call_counts(text) == {
        "fused_frame_attention": 1, "fused_frame_attention_bwd": 1}
    assert "all-reduce" in text


@pytest.mark.parametrize("slab", GROUP_NORM_SLABS[:2],
                         ids=lambda s: "x".join(map(str, s)))
def test_sharded_group_norm_compiles(mesh4, slab):
    """The kernel under ``jax.shard_map`` with the specs of
    ``parallel.mesh.make_sharded_group_norm_fn``: the sample axis over
    ``data × frames``, scale and bias replicated."""
    rows, c = slab
    sample_spec = P((AXIS_DATA, AXIS_FRAMES), None, None)
    fn = jax.shard_map(
        functools.partial(fused_group_norm, num_groups=32, eps=1e-5,
                          act="silu"),
        mesh=mesh4, in_specs=(sample_spec, P(None), P(None)),
        out_specs=sample_spec, check_vma=False,
    )
    x = jax.ShapeDtypeStruct((16, rows, c), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, sample_spec))
    sb = jax.ShapeDtypeStruct((c,), jnp.float32,
                              sharding=NamedSharding(mesh4, P(None)))
    kernels = _kernels(fn, x, sb, sb)
    assert kernels == {"fused_group_norm": 1}
