"""Device time by named scope on two small traces recorded on the chip
(`data/trace_cnn_{fp32,q8}_scoped.json`: two forwards, one call of the
pool loop over 2 batches, each event by its whole HLO instruction name;
the compiled program's scope map and its one-call schedule). A traced
run's record keeps each event by its op name alone, so the record here
drops the numbers and the attribution has to find them again."""
import json

import pytest

import run
import scopes
import trace_reduce
from conftest import BENCH

DATA = BENCH / "tests" / "data"
CHIP = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CONV = {"fp32": "_conv_jit", "q8": "_conv_q8_jit"}


def scoped(mode):
    return json.loads((DATA / f"trace_cnn_{mode}_scoped.json").read_text())


def scoped_record(mode, drop=None):
    """The two forwards reduced as a traced run reduces them, and
    attributed; `drop` leaves out the first event of that op."""
    d = scoped(mode)
    ev = {"device": [(trace_reduce.op_name(n), s, dur)
                     for n, s, dur in d["device"]],
          "host": [tuple(e) for e in d["host"]]}
    if drop:
        ev["device"].remove(next(e for e in ev["device"] if e[0] == drop))
    t = trace_reduce.window(ev, trace_reduce.spans(ev["host"],
                                                   "bench.window"))
    t["batches"] = 2
    t["scopes"] = scopes.attribute(
        scopes.align(t["session"], d["schedule"], calls=1), d["program"],
        calls=1)
    cfg = json.loads((BENCH / "configs" / "resnet18-cifar10.json")
                     .read_text())
    return {"kind": "cnn", "mode": mode, "batch": 256, "device": CHIP,
            "config": cfg, "trace": t}


@pytest.mark.parametrize("mode", ["fp32", "q8"])
def test_align_finds_every_instruction_name(mode):
    """The order the compiled text gives is the order the chip ran the
    events in: each event gets back the name the trace gave it."""
    d = scoped(mode)
    named = [tuple(e) for e in d["device"]]
    traced = [(trace_reduce.op_name(n), s, dur) for n, s, dur in named]
    got = scopes.align(traced, d["schedule"], calls=1)
    assert got == [e for e in named if trace_reduce.op_name(e[0])
                   not in trace_reduce.CONTAINERS]


@pytest.mark.parametrize("mode", ["fp32", "q8"])
def test_scoped_trace_maps_every_event(mode):
    rec = scoped_record(mode)
    a = rec["trace"]["scopes"]
    assert a["unmapped_n"] == 0
    # every instruction of the program ran as often as the program says
    assert all(r["n"] == r["want"] for r in a["rows"])
    calls = [e for e in scoped(mode)["device"]
             if trace_reduce.op_name(e[0]) == CONV[mode]]
    assert len(calls) == 2 * 20 and len({e[0] for e in calls}) == 20
    # twenty conv layers, each its own scope
    assert len(scopes.kernel_scopes(rec, [CONV[mode]])) == 20


@pytest.mark.parametrize("mode", ["fp32", "q8"])
def test_scopes_add_up_to_the_busy_time(mode):
    rec = scoped_record(mode)
    a = rec["trace"]["scopes"]
    table = scopes.by_scope(a, 2)
    own = sum(o for _, _, o, _ in table)
    assert own == pytest.approx(a["busy_ns"] * 1e-6 / 2, rel=0.01)
    unscoped = next(o for s, _, o, _ in table if s == "")
    assert unscoped < 0.02 * own


@pytest.mark.parametrize("mode,roof", [("fp32", "cadc_conv_roofline.fp32"),
                                       ("q8", "cadc_conv_roofline.q8")])
def test_scoped_traces_read_as_before(mode, roof):
    """The existing readers read these traces as they read a run's: the
    roofline near its ledger reading (the host window of this one call
    opens before its dispatch, so idle reads a few percent)."""
    rec = scoped_record(mode)
    want = {"fp32": 15.9, "q8": 10.4}[mode]
    assert run.read_metric(roof, rec) == pytest.approx(want, abs=0.3)
    assert 0 <= run.read_metric("device_idle.cnn", rec) < 10


def test_span_metrics_on_the_recorded_traces():
    fp32, q8 = scoped_record("fp32"), scoped_record("q8")
    assert 3.5 < run.read_metric("conv_glue_ms.cnn", fp32) < 4.5
    assert 1.2 < run.read_metric("conv_glue_ms.cnn", q8) < 2.0
    assert 2.0 < run.read_metric("bn_act_ms.cnn", fp32) < 3.0
    # q8: BN and ReLU fuse into the next layer's quantisation
    assert run.read_metric("bn_act_ms.cnn", q8) < 0.3
    assert 2.5 < run.read_metric("quantize_ms.q8", q8) < 3.5
    assert run.read_metric("quantize_ms.q8", fp32) is None


def test_fusions_that_span_scopes_go_to_their_root():
    prog = scoped("fp32")["program"]
    bn_relu = [e for e in prog.values() if e["op"] == "add_maximum_fusion"]
    assert bn_relu and all(e["scope"].endswith(("/relu", "/residual"))
                           and any("/bn" in s for s in e["spans"])
                           for e in bn_relu)
    prog = scoped("q8")["program"]
    quant = [e for e in prog.values()
             if e["op"] == "round-nearest-even_convert_fusion"]
    assert quant and all(e["scope"].endswith("/quantize_act")
                         for e in quant)
    assert any(any(s.endswith("/relu") for s in e["spans"]) for e in quant)


@pytest.mark.parametrize("name,op", [
    ("conv_glue_ms.cnn", "pad_bitcast_fusion"),
    ("bn_act_ms.cnn", "add_maximum_fusion")])
def test_a_lost_event_silences_the_span_metric(name, op):
    assert run.read_metric(name, scoped_record("fp32")) is not None
    assert run.read_metric(name, scoped_record("fp32", drop=op)) is None
