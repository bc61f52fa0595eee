"""The per-layer metrics' readers on small traces recorded on the chip
(`data/`): which ops are the kernels, how many calls a forward or a step
makes, and shares that stay inside [0, 100]."""
import json

import pytest

import run
import trace_reduce
from conftest import BENCH

DATA = BENCH / "tests" / "data"
CHIP = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def recorded(name):
    d = json.loads((DATA / name).read_text())
    return {"device": [tuple(e) for e in d["device"]],
            "host": [tuple(e) for e in d["host"]]}


def cnn_record():
    ev = recorded("trace_cnn_fp32.json")
    t = trace_reduce.window(ev, trace_reduce.spans(ev["host"],
                                                   "bench.window"))
    t["batches"] = 2
    return {"kind": "cnn", "mode": "fp32", "batch": 256, "device": CHIP,
            "config": config("resnet18-cifar10"), "trace": t}


def serve_record():
    ev = recorded("trace_serve_decode.json")
    t = trace_reduce.window(ev, trace_reduce.spans(ev["host"], "bench.step"))
    t["steps"] = [{"a": s / 1e9, "b": (s + d) / 1e9, "n_prefill_calls": 0,
                   "n_decode_calls": 1, "prefilled": [],
                   "decoded": [200 + k] * 8}
                  for k, (_, s, d) in enumerate(ev["host"])]
    return {"kind": "serve", "traffic": "chat", "device": CHIP,
            "config": config("phi4-mini-cadc"), "trace": t}


def test_op_names():
    assert trace_reduce.op_name(
        "%_conv_q8_jit.20 = f32[256,32,32,64] custom-call(s8[1])") == \
        "_conv_q8_jit"
    assert trace_reduce.op_name("%while.3 = (s32[]) while()") == "while"
    assert trace_reduce.op_name("%fusion = bf16[2] fusion()") == "fusion"
    assert trace_reduce.op_name("%pad.12.clone = f32[2] pad()") == "pad"


def test_cnn_trace_kernels_and_shares():
    rec = cnn_record()
    t = rec["trace"]
    assert trace_reduce.kernel_ns(t["device"], t["windows"], "_conv_jit")[1] \
        == 2 * 20
    # the classifier runs the matmul kernel, not the conv
    assert trace_reduce.kernel_ns(t["device"], t["windows"],
                                  "cadc_matmul_pallas")[1] == 2
    roof = run.read_metric("cadc_conv_roofline.fp32", rec)
    mfu = run.read_metric("mfu.cnn", rec)
    idle = run.read_metric("device_idle.cnn", rec)
    assert 5 < roof < 30
    assert 3 < mfu < roof
    assert 0 <= idle < 5
    assert run.read_metric("cadc_conv_roofline.q8", rec) is None
    assert [n for n, _ in t["breakdown"]["device_ops"]][0] == "_conv_jit"


def test_cnn_roofline_needs_every_call():
    rec = cnn_record()
    rec["trace"]["batches"] = 3
    assert run.read_metric("cadc_conv_roofline.fp32", rec) is None


def test_cnn_roofline_counts_calls_at_the_window_edge():
    """The trace maps the device clock onto the host's only to within a
    millisecond or so: a forward's first conv can then lie just before the
    host window, and the roofline still counts every call."""
    want = run.read_metric("cadc_conv_roofline.fp32", cnn_record())
    ev = recorded("trace_cnn_fp32.json")
    win = trace_reduce.spans(ev["host"], "bench.window")
    first = next(e for e in ev["device"] if e[0] == "_conv_jit")
    shift = first[1] + first[2] - win[0][0] + 1000
    ev["device"] = [(n, s - shift, d) for n, s, d in ev["device"]]
    t = trace_reduce.window(ev, win)
    t["batches"] = 2
    assert trace_reduce.kernel_ns(t["device"], t["windows"],
                                  "_conv_jit")[1] < 2 * 20
    rec = dict(cnn_record(), trace=t)
    assert run.read_metric("cadc_conv_roofline.fp32", rec) == \
        pytest.approx(want)


def test_serve_trace_kernels_and_shares():
    rec = serve_record()
    t = rec["trace"]
    assert trace_reduce.kernel_ns(t["device"], t["windows"],
                                  "cadc_matmul_pallas")[1] == 3 * 7 * 8
    roof = run.read_metric("cadc_matmul_roofline.serve", rec)
    mfu = run.read_metric("mfu.serve", rec)
    idle = run.read_metric("device_idle.serve", rec)
    assert 0 < roof <= 100
    assert 0 < mfu < 5
    assert 0 <= idle < 30
    names = [n for n, _ in t["breakdown"]["device_ops"]]
    assert "while" not in names
    assert names[0] == "convert_element_type"


@pytest.mark.parametrize("name", ["cadc_conv_roofline.fp32", "mfu.cnn",
                                  "device_idle.cnn", "mfu.serve",
                                  "cadc_matmul_roofline.serve",
                                  "device_idle.serve"])
def test_no_trace_no_reading(name):
    rec = {"kind": "cnn", "mode": "fp32", "device": CHIP}
    assert run.read_metric(name, rec) is None
