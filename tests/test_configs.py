"""Every shipped config must bind cleanly to its CLI entry point.

The reference treats its six tune/p2p YAML pairs as the de-facto regression
suite (SURVEY §4); here the schema contract is pinned mechanically: each
YAML parses, provides every required argument of its `main(...)`, uses only
known parameter names (a typo'd key would silently fall into **unused), and
points at a clip directory that exists for the shipped scenes.
"""

import glob
import inspect
import os

import pytest

from videop2p_tpu.cli.common import load_config
from videop2p_tpu.cli.run_tuning import main as tune_main
from videop2p_tpu.cli.run_videop2p import main as p2p_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "configs", "*.yaml")))
# referenced by the reference's configs but not shipped there either —
# data/ ships synthesized stand-ins for these two scenes
SHIPPED_CLIPS = {"car", "motorbike", "penguin_ice", "rabbit", "tiger", "bird_forest"}


def _required(fn):
    sig = inspect.signature(fn)
    return {
        n for n, p in sig.parameters.items()
        if p.default is inspect.Parameter.empty
        and p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)
    }


def _known(fn):
    return set(inspect.signature(fn).parameters) - {"unused"}


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p) for p in CONFIGS])
def test_config_binds_to_entry_point(path):
    cfg = load_config(path)
    is_tune = path.endswith("-tune.yaml")
    fn = tune_main if is_tune else p2p_main
    missing = _required(fn) - set(cfg)
    assert not missing, f"{path} misses required args {missing}"
    unknown = set(cfg) - _known(fn)
    assert not unknown, f"{path} has keys no parameter consumes: {unknown}"

    if is_tune and cfg.get("model_family", "unet3d") != "unet3d":
        return  # a token model tunes on a document of ids, not on a clip
    clip = cfg["train_data"]["video_path"] if is_tune else cfg["image_path"]
    name = os.path.basename(clip.rstrip("/"))
    if name in SHIPPED_CLIPS:
        assert os.path.isdir(os.path.join(ROOT, clip)), f"{clip} not shipped"

    if not is_tune:
        assert len(cfg["prompts"]) >= 2
        assert cfg["prompt"] == cfg["prompts"][0], (
            f"{path}: source prompt must open the prompts list"
        )


def test_token_model_config_loads_and_builds_the_published_model():
    """The token model's YAML loads through ``load_config``, names a known
    family, and its ``model`` dict is the published config.json at this
    chip's share (ISSUE 28: 16 of 256 experts, 8 of 128 heads, 1/8 of the
    vocabulary, 1 dense + 4 expert layers; no width cut)."""
    from videop2p_tpu.cli.common import MODEL_FAMILIES, check_model_family
    from videop2p_tpu.models.deepseek import DeepSeekV32Config

    cfg = load_config(os.path.join(ROOT, "configs", "deepseek-v32-s16-tune.yaml"))
    assert check_model_family(cfg["model_family"]) == "deepseek_v32"
    assert cfg["model_family"] in MODEL_FAMILIES
    model = DeepSeekV32Config.from_dict(cfg["model"])
    published = DeepSeekV32Config()
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "vocab_size": 16160, "experts_held": (0, 16), "heads_held": (0, 8)}
    for f in inspect.signature(DeepSeekV32Config).parameters:
        assert getattr(model, f) == cut.get(f, getattr(published, f)), f
    assert list(cfg["trainable_modules"]) == ["q_a_proj", "q_b_proj"]
    assert cfg["train_data"]["n_tokens"] == 16384


def test_hybrid_token_model_config_loads_and_builds_the_published_model():
    """The hybrid token model's YAML loads through ``load_config``, names a
    known family, and its ``model`` dict is the published config.json at
    this chip's share (ISSUE 32: 18 of 72 experts, 8 of 32 query heads, 32
    of 128 Mamba heads, 1/4 of the vocabulary, one period of ``layer_types``;
    no width cut)."""
    from videop2p_tpu.cli.common import MODEL_FAMILIES, check_model_family
    from videop2p_tpu.models.granite_hybrid import GraniteHybridConfig

    cfg = load_config(os.path.join(
        ROOT, "configs", "granite-4.0-h-small-s4-tune.yaml"))
    assert check_model_family(cfg["model_family"]) == "granitemoehybrid"
    assert cfg["model_family"] in MODEL_FAMILIES
    model = GraniteHybridConfig.from_dict(cfg["model"])
    model.check()
    published = GraniteHybridConfig()
    cut = {"num_hidden_layers": 10, "layer_types": published.layer_types[:10],
           "vocab_size": 25088, "experts_held": (0, 18), "heads_held": (0, 8),
           "mamba_heads_held": (0, 32)}
    for f in inspect.signature(GraniteHybridConfig).parameters:
        assert getattr(model, f) == cut.get(f, getattr(published, f)), f
    assert list(cfg["trainable_modules"]) == ["q_proj", "in_proj_c"]
    assert cfg["train_data"]["n_tokens"] == 32768
    with pytest.raises(ValueError, match="unknown GraniteHybridConfig keys"):
        GraniteHybridConfig.from_dict({**cfg["model"], "n_group": 8})


def test_third_token_family_config_loads_and_builds_the_published_model():
    """Command A+'s YAML loads through ``load_config``, names a known family,
    and its ``model`` dict is the published config.json at this chip's share
    (ISSUE 34: 16 of 128 experts, 16 of 128 query heads on one key / value
    head, 2048 of the 16384 shared-expert columns, 1/8 of the vocabulary, one
    period of ``layer_types``; no width cut)."""
    from videop2p_tpu.cli.common import MODEL_FAMILIES, check_model_family
    from videop2p_tpu.models.cohere2_moe import Cohere2MoeConfig

    cfg = load_config(os.path.join(ROOT, "configs", "command-a-plus-s8-tune.yaml"))
    assert check_model_family(cfg["model_family"]) == "cohere2_moe"
    assert cfg["model_family"] in MODEL_FAMILIES
    model = Cohere2MoeConfig.from_dict(cfg["model"])
    model.check()
    published = Cohere2MoeConfig()
    cut = {"num_hidden_layers": 4, "layer_types": published.layer_types[:4],
           "vocab_size": 32768, "experts_held": (0, 16), "heads_held": (0, 16),
           "shared_columns_held": (0, 2048)}
    for f in inspect.signature(Cohere2MoeConfig).parameters:
        assert getattr(model, f) == cut.get(f, getattr(published, f)), f
    assert model.kv_heads_held == (0, 1)
    assert list(cfg["trainable_modules"]) == ["q_proj"]
    assert cfg["train_data"]["n_tokens"] == 32768
    with pytest.raises(ValueError, match="unknown Cohere2MoeConfig keys"):
        Cohere2MoeConfig.from_dict({**cfg["model"], "n_group": 8})


def test_unknown_model_family_is_rejected_with_the_known_ones():
    from videop2p_tpu.cli.common import check_model_family

    with pytest.raises(ValueError) as err:
        check_model_family("sdxl")
    assert "'sdxl'" in str(err.value)
    assert "unet3d" in str(err.value) and "deepseek_v32" in str(err.value)
    assert "granitemoehybrid" in str(err.value)
    assert "cohere2_moe" in str(err.value)
    with pytest.raises(ValueError, match="known:"):
        tune_main(pretrained_model_path=None, output_dir="unused",
                  train_data={}, model_family="nope")
