"""The control of `correct` at a size a CPU test can hold: the plain
reference one precision below the configuration's, put in the program's
place, reads far above the program on the same checked sample. On the
chip `bench/control.py` takes the same readings at the cells' own sizes;
PERF.md gives them beside the limits."""
import json
import time

import pytest

import run
import smoke
from conftest import BENCH

ROOT = BENCH.parent


def readings(cell, cfg, mix, seed):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = run.run_loaded(bench, cell, cfg, mix, seed, 1.5, False,
                         t_start=time.perf_counter(), require_chip=False,
                         control=True)
    return out, out["record"]["control"]


@pytest.mark.parametrize("mode", ["fp32", "q8"])
def test_cnn_control_reads_above_the_program(mode):
    cell = smoke.cell("resnet18-cifar10", f"{mode}-b256")
    out, ctl = readings(cell, smoke.resnet(), smoke.cnn_mix(f"{mode}-b256"),
                        2**31 + 40)
    assert out["correct"] is True
    for name, limit in smoke.resnet()["limits"][mode].items():
        got = out["checks"][name]["value"]
        assert ctl[name] > max(100 * got, 1e-3)
        assert ctl[name] > limit


def test_serve_control_reads_above_the_program():
    cell = smoke.cell("phi4-mini-cadc", "chat")
    out, ctl = readings(cell, smoke.phi4(), smoke.serve_mix("chat"),
                        2**31 + 41)
    assert out["correct"] is True
    got = out["checks"]["token_logit_gap"]["value"]
    assert ctl["token_logit_gap"] > 3 * got
    assert ctl["bf16_logit_err"] > 0.0
