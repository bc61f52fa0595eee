"""The control of `correct` at a size a CPU test can hold: the plain
reference one precision below the configuration's, put in the program's
place, reads far above the program on the same checked sample. On the
chip `bench/control.py` takes the same readings at the cells' own sizes;
PERF.md gives them beside the limits."""
import json
import time

import pytest

import control
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
    assert control.control_correct(out) is False


# At smoke widths the logits are far smaller than at the cell's, and so
# are both readings: the program 0 and the control 0.015 on this seed,
# under the cell's limit of 0.05. A limit of the same making at this
# size, about twice the bf16-rounded reference's logit error (0.0037),
# lies between them.
SMOKE_GAP_LIMIT = 0.008


def test_serve_control_reads_above_the_program():
    cell = smoke.cell("phi4-mini-cadc", "chat")
    cfg = smoke.phi4()
    cfg["limits"] = dict(cfg["limits"], token_logit_gap=SMOKE_GAP_LIMIT)
    out, ctl = readings(cell, cfg, smoke.serve_mix("chat"), 2**31 + 41)
    assert out["correct"] is True
    got = out["checks"]["token_logit_gap"]["value"]
    assert ctl["token_logit_gap"] > 3 * got
    assert ctl["bf16_logit_err"] > 0.0
    checks = control.control_checks(out)
    assert set(checks) == {"token_logit_gap"}
    assert checks["token_logit_gap"]["limit"] == SMOKE_GAP_LIMIT
    assert control.control_correct(out) is False, checks
