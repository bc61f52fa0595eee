"""Smoke-size stand-ins for the benchmark's configurations and mixes, for
the CPU tests: the same files with the widths, depth, batch and lengths
cut, and the kernels on their XLA path (or the Pallas interpreter)."""
import copy
import json

from conftest import BENCH


def load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def resnet(width=8, kernel="xla"):
    cfg = load("configs", "resnet18-cifar10")
    cfg.update(width=width, kernel=kernel, calibration_images=8)
    return cfg


def cnn_mix(name, batch=4, pool=2):
    mix = load("traffic", name)
    mix.update(batch=batch, pool=pool, in_flight=2)
    return mix


SMALL_LM = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=128,
                vocab_size=512, crossbar_size=64)


def phi4(dtype=None):
    cfg = load("configs", "phi4-mini-cadc")
    cfg.update(SMALL_LM)
    o = cfg["program"]["overrides"]
    o.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab_size=512, crossbar_size=64, kernel_impl="xla",
             attn_chunk=64)
    if dtype:
        o["dtype"] = dtype
    return cfg


def serve_mix(name):
    mix = copy.deepcopy(load("traffic", name))
    mix.update(prompt=dict(mix["prompt"], min=8, max=40, median=16),
               output=dict(mix["output"], min=4, max=24, median=8),
               max_len=64, check_requests=16)
    if mix["arrival"] == "poisson":
        mix["rate_per_s"] = 4.0
    else:
        mix["requests"] = 40
    mix.get("engine", {}).pop("n_slots", None)
    return mix


def cell(config, traffic):
    return {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": 1}
