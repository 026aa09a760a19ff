"""Model FLOPs per token against each configuration's parameter count."""

import json
import math

import jax
import pytest

import harness
from flops import family, peak, train_flops_per_token

CONFIGS = sorted((harness.HERE / "configs").glob("*.json"))


def _program_params(cfg_file):
    """(all parameters, those in vectors: norm gains and biases) of the program."""
    from repro.models import build_model
    from repro.models import params as PM
    cell = harness.Cell("x", 1, json.loads(cfg_file.read_text()), {}, {}, [], [])
    layout = build_model(harness.model_config(cell), mesh=None).layout()
    leaves = jax.tree_util.tree_flatten_with_path(
        layout, is_leaf=lambda x: isinstance(x, PM.ParamInfo))[0]
    vectors = 0
    for path, info in leaves:
        stacked = path[0].key == "layers"
        if len(info.shape) - stacked == 1:
            vectors += math.prod(info.shape)
    return PM.param_count(layout), vectors


@pytest.mark.parametrize("cfg_file", CONFIGS, ids=[c.stem for c in CONFIGS])
def test_matmul_params_are_the_program_params_less_vectors(cfg_file):
    c = json.loads(cfg_file.read_text())
    total, vectors = _program_params(cfg_file)
    assert family(c).matmul_params(c) == total - vectors


def test_qwen_0_5b_counts():
    c = json.loads((harness.HERE / "configs" / "qwen1.5-0.5b.json").read_text())
    total, _ = _program_params(harness.HERE / "configs" / "qwen1.5-0.5b.json")
    assert total == 463_987_712                      # the bring-up's count
    fam = family(c)
    assert fam.matmul_params(c) == 463_863_808
    # 6N plus causal attention: 3 x 2 x 2 x heads x head_dim x (S + 1) / 2 x layers
    assert fam.attention_flops_per_token(c, 128) == 6 * 1024 * 129 * 24
    per_step = train_flops_per_token(c, 128) * 32 * 128
    assert per_step == (6 * 463_863_808 + 6 * 1024 * 129 * 24) * 4096
    assert train_flops_per_token(c, 2048) * 2 * 2048 == \
        (6 * 463_863_808 + 6 * 1024 * 2049 * 24) * 4096


def test_peaks_are_keyed_by_device_kind():
    assert peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peak("TPU v99")
