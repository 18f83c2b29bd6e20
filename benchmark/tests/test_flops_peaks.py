"""flops.py against counts made by hand; the peak table."""

import json
import os

import pytest

from benchmark.harness import flops, registry
from benchmark.harness.peaks import peaks
from helpers import ROOT


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_flops_per_token_by_hand():
    cfg = _config("gpt2-medium")
    # One layer: 12 * 1024^2 multiply-adds in its four matmuls, plus
    # causal attention over (1024 + 1) / 2 keys, twice, 1024 wide.
    layer_macs = 12 * 1024 * 1024 + 2 * 1024 * 1025 / 2
    head_macs = 1024 * 50257
    forward = 2 * (24 * layer_macs + head_macs)
    assert forward == pytest.approx(757_286_912, rel=1e-12)
    assert flops.gpt_forward_flops_per_token(cfg, 1024) == forward
    assert flops.gpt_train_flops_per_token(cfg, 1024) == 3 * forward
    # the family's own statement, which the runner asks
    family = registry.load_model_builder(cfg["family"], ROOT)
    assert family.train_flops_per_item(cfg, {"seq_len": 1024}) == 3 * forward
    # sizes the program was built with go over the file's
    assert family.train_flops_per_item(
        cfg, {"seq_len": 1024, "n_layer": 12}) < 3 * forward


def test_resnet50_forward_flops_by_hand():
    cfg = _config("resnet50-v1.5")
    # Multiply-adds, stage by stage (v1.5: stride on the 3x3).
    stem = 112 * 112 * 49 * 3 * 64
    s1 = (56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
          + 2 * 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    s2 = (56 * 56 * 256 * 128 + 28 * 28 * (9 * 128 * 128 + 128 * 512)
          + 28 * 28 * 256 * 512
          + 3 * 28 * 28 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
    s3 = (28 * 28 * 512 * 256 + 14 * 14 * (9 * 256 * 256 + 256 * 1024)
          + 14 * 14 * 512 * 1024
          + 5 * 14 * 14 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
    s4 = (14 * 14 * 1024 * 512 + 7 * 7 * (9 * 512 * 512 + 512 * 2048)
          + 7 * 7 * 1024 * 2048
          + 2 * 7 * 7 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
    macs = stem + s1 + s2 + s3 + s4 + 2048 * 1000
    # the figure everyone quotes for ResNet-50 v1.5: 4.09 G multiply-adds
    assert macs == pytest.approx(4.09e9, rel=0.01)
    assert flops.resnet_forward_flops_per_image(cfg) == 2 * macs
    assert flops.resnet_train_flops_per_image(cfg) == 6 * macs
    family = registry.load_model_builder(cfg["family"], ROOT)
    assert family.train_flops_per_item(cfg, {"image_size": 224}) == 6 * macs


def test_flash_lower_bound_and_its_side():
    need_flops, need_bytes = flops.flash_train_flops_bytes(
        batch=8, heads=16, seq_len=1024, head_dim=64, layers=24)
    assert need_flops == 7 * 1024 * 1024 * 64 * 8 * 16 * 24
    assert need_bytes == 12 * 1024 * 64 * 2 * 8 * 16 * 24
    seconds, side = flops.roofline_seconds(need_flops, need_bytes,
                                           peaks("TPU v5 lite"))
    assert side == "compute"
    assert seconds == pytest.approx(need_flops / 197e12)
    assert flops.roofline_seconds(1.0, 1e6, peaks("TPU v5 lite"))[1] \
        == "memory"


def test_peaks_known_and_unknown():
    row = peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")
