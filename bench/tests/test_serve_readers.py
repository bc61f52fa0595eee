"""The serving cells' metric readers on smoke-size serve records, one of
each mix, made by the serving driver on the CPU: each reads a finite
number where its cell reports it, and nothing from another kind of
record. The readers of the device trace need a chip's trace; they are
read on a recorded one in `test_recorded_trace.py`."""
import json
import math
import time

import pytest

import run
import smoke
from conftest import BENCH

ROOT = BENCH.parent
B = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVE_CELLS = {"chat": "phi4-mini-cadc.chat",
               "offline-long": "phi4-mini-cadc.offline-long"}
# the readers of the serving cells that need no device trace, by mix
HOST_READERS = {
    "chat": ["output_tokens_per_s", "host_ms_per_step.serve",
             "decode_step_ms.serve", "prefill_ms.chat"],
    "offline-long": ["output_tokens_per_s", "host_ms_per_step.serve",
                     "decode_step_ms.serve", "prefill_ms.offline"],
}
TRACE_READERS = ["mfu.serve", "cadc_matmul_roofline.serve",
                 "device_idle.serve"]


@pytest.fixture(scope="module")
def records():
    out = {}
    for i, traffic in enumerate(SERVE_CELLS):
        cell = smoke.cell("phi4-mini-cadc", traffic)
        got = run.run_loaded(B, cell, smoke.phi4(), smoke.serve_mix(traffic),
                             2**31 + 60 + i, 2.0, False,
                             t_start=time.perf_counter(), require_chip=False)
        assert got["correct"] is True
        out[traffic] = got["record"]
    return out


@pytest.mark.parametrize("traffic,name", [(t, n) for t in sorted(HOST_READERS)
                                          for n in HOST_READERS[t]])
def test_reader_on_a_smoke_serve_record(records, traffic, name):
    m = next(m for m in B["end_to_end"] + B["per_layer"]
             if m["name"] == name)
    assert SERVE_CELLS[traffic] in m["workloads"]
    v = run.read_metric(name, records[traffic])
    assert v is not None and math.isfinite(v) and v > 0, (name, v)


def test_prefill_readers_read_their_own_mix(records):
    assert run.read_metric("prefill_ms.chat", records["offline-long"]) is None
    assert run.read_metric("prefill_ms.offline", records["chat"]) is None


@pytest.mark.parametrize("name", sorted(set(sum(HOST_READERS.values(), []))
                                        | set(TRACE_READERS)))
def test_reader_reads_nothing_from_a_cnn_record(name):
    rec = {"kind": "cnn", "mode": "fp32", "traffic": "fp32-b256",
           "images": 512, "window_s": 1.0, "seconds": 1.0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert run.read_metric(name, rec) is None
