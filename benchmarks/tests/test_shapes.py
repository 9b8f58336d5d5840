"""The shape functions a roofline share is a share of."""
import json

from conftest import ROOT

from benchmarks.trace import shapes


def config(name):
    return json.loads((ROOT / "benchmarks" / "configs"
                       / f"{name}.json").read_text())


def test_resnet50_conv_flops_count_two_per_multiply_add():
    cfg = config("resnet50")
    convs = shapes.resnet_convs(cfg)
    assert len(convs) == 53
    assert convs[0] == ("stem", 112, 112, 7, 7, 3, 64)
    assert convs[-1][1:3] == (7, 7) and convs[-1][-1] == 2048
    fwd = shapes.resnet_forward_conv_flops(cfg)
    # 3.86 G multiply-adds with the stride on the first 1x1 (the zoo's
    # graph): 7.71 GFLOP, not the 4.1 "GFLOP" tools/perf_dossier.py
    # used, which counted v1.5's multiply-adds as FLOPs
    assert fwd == 7_711_850_496
    stem = 2 * 112 * 112 * 7 * 7 * 3 * 64
    assert shapes.resnet_train_conv_flops(cfg) == 3 * fwd - stem


def test_mistral_decode_step_bytes():
    cfg = config("mistral-7b-v0.3-6l")
    assert shapes.lm_layer_params(cfg) == 218_103_808
    # 6 layers and the untied head in bf16
    assert shapes.lm_decode_weight_bytes(cfg) == 2 * (
        6 * 218_103_808 + 4096 * 32768)
    # 6 layers x (K and V) x 8 heads x 128 x 2 bytes
    assert shapes.lm_kv_bytes_per_token(cfg) == 24_576
