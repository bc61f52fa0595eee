"""The operation and parameter counters against published totals."""
import json

import pytest

import flops
import peaks
from conftest import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_resnet18_cifar_macs_per_image():
    # ResNet-18, CIFAR variant, width 64: 555.4 M multiply-adds an image
    macs = flops.resnet18_macs_per_image(config("resnet18-cifar10"))
    assert macs == pytest.approx(555.42e6, rel=1e-4)


def test_resnet18_convs_are_the_forwards():
    convs = flops.resnet18_convs(config("resnet18-cifar10"))
    # stem, 8 blocks of two 3x3 convs, three 1x1 projections
    assert len(convs) == 1 + 16 + 3
    assert sum(1 for c in convs if c[3] == 1) == 3
    assert convs[-1] == (4, 512, 512, 3, 1)


def test_phi4_mini_parameter_counts():
    cfg = config("phi4-mini-cadc")
    assert flops.decoder_layer_params(cfg) == pytest.approx(100.7e6, rel=1e-3)
    assert flops.decoder_embed_params(cfg) == pytest.approx(614.6e6, rel=1e-3)


def test_decoder_kernel_calls_unpadded_rows():
    cfg = config("phi4-mini-cadc")
    calls = flops.decoder_kernel_calls(cfg, 3)
    assert len(calls) == 7 * cfg["num_hidden_layers"]
    ops, byt = calls[0]
    d = cfg["hidden_size"]
    assert ops == 2 * 3 * d * d
    assert byt == (3 * d + d * d) * 2 + 3 * d * 4


def test_decoder_model_ops_decode_and_prefill():
    cfg = config("phi4-mini-cadc")
    lin = flops.decoder_layer_params(cfg) - 2 * cfg["hidden_size"]
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    one = flops.decoder_model_ops(cfg, 1, 0, 1)
    attn = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    assert one == pytest.approx(
        cfg["num_hidden_layers"] * (2 * lin + attn) + head)
    # a decode token attends to its context and itself
    c = flops.decoder_model_ops(cfg, 1, 99, 1) - one
    assert c == pytest.approx(cfg["num_hidden_layers"] * attn * 99)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
