"""The lfm2 cell's per-layer readers on a synthetic record: two traced
engine steps, one with a prefill call and one decode call, one with a
decode call alone, each program with its routed-rows counter."""
import numpy as np
import pytest

import flops_lfm2
import peaks
import run
import smoke

CFG = smoke.load("configs", "lfm2-8b-a1b-cadc")
N_MOE = CFG["num_hidden_layers"] - CFG["num_dense_layers"]
DEC = [[4] * 8] * N_MOE           # 32 slots x 4 / 32 experts, each layer
PRE = [[30] * 8] * N_MOE
MS = 1_000_000


def events(t0, n, each_ns, gap_ns=1000):
    return [("cadc_gmm_pallas", t0 + i * (each_ns + gap_ns), each_ns)
            for i in range(n)]


def record():
    per = 3 * N_MOE
    dev = (events(0, per, 200_000) + events(10 * MS, per, 150_000)
           + events(30 * MS, per, 160_000))
    steps = [
        {"a": 0.0, "b": 0.02, "prefill_s": 0.008, "decode_s": 0.01,
         "n_prefill_calls": 1, "n_decode_calls": 1, "prefilled": [60],
         "decoded": [100] * 31, "routed": [["prefill", PRE],
                                           ["decode", DEC]]},
        {"a": 0.03, "b": 0.045, "prefill_s": 0.0, "decode_s": 0.012,
         "n_prefill_calls": 0, "n_decode_calls": 1, "prefilled": [],
         "decoded": [101] * 32, "routed": [["decode", DEC]]},
    ]
    return {
        "kind": "serve", "config": CFG, "t_end": 1.0, "seconds": 1.0,
        "device": {"kind": "TPU v5 lite"}, "steps": steps, "requests": [],
        "trace": {"steps": steps, "windows": [[0, 20 * MS], [30 * MS, 45 * MS]],
                  "step_spans": [[0, 20 * MS], [30 * MS, 45 * MS]],
                  "device": dev},
    }


def read(name, rec):
    return run.read_metric(name, rec)


def test_rows_per_expert():
    assert read("rows_per_expert.lfm2", record()) == pytest.approx(4.0)


def test_gmm_ms_per_decode_reads_decode_only_steps():
    got = read("gmm_ms_per_decode.lfm2", record())
    assert got == pytest.approx(3 * N_MOE * 0.16)


def test_cadc_gmm_roofline():
    rec = record()
    p = peaks.peaks("TPU v5 lite")
    least = sum(max(o / p["bf16"], b / p["hbm_bytes_per_s"])
                for rows in (PRE, DEC, DEC)
                for o, b in flops_lfm2.gmm_calls(CFG, rows))
    ns = 3 * N_MOE * (200_000 + 150_000 + 160_000)
    got = read("cadc_gmm_roofline.lfm2", rec)
    assert got == pytest.approx(100 * least / (ns * 1e-9))
    assert 0 < got < 100


def test_cadc_gmm_roofline_needs_every_call():
    rec = record()
    rec["trace"]["device"] = rec["trace"]["device"][1:]
    assert read("cadc_gmm_roofline.lfm2", rec) is None


def test_mfu_counts_routed_rows():
    rec = record()
    got = read("mfu.lfm2", rec)
    rows = sum(np.sum(r) for s in rec["steps"] for _, r in s["routed"])
    assert rows == 8 * N_MOE * (30 + 4 + 4)
    assert 0 < got < 100
    for s in rec["trace"]["steps"]:
        s["routed"] = [[k, [[0] * 8] * N_MOE] for k, _ in s["routed"]]
    assert read("mfu.lfm2", rec) < got


def test_readers_find_nothing_in_phi4_records():
    rec = record()
    for s in rec["steps"]:
        del s["routed"]
    del rec["trace"]["step_spans"]
    for name in ("rows_per_expert.lfm2", "gmm_ms_per_decode.lfm2",
                 "cadc_gmm_roofline.lfm2", "mfu.lfm2"):
        assert read(name, rec) is None, name


def test_cadc_gmm_roofline_holds_weights_read_from_vmem():
    """Decode calls as a TPU v5e ran them: 18 a program, 26.5 us each,
    6.65 of 8 held experts with rows (0.478 ms a decode). Their weights
    at HBM bandwidth alone would take 2.2x that; they reach the kernel
    from VMEM, and the share stays under the peak."""
    rows = [[3, 3, 2, 2, 2, 2, 2, 0]] * N_MOE
    d, f = CFG["hidden_size"], CFG["moe_intermediate_size"]
    weights = 3 * 7 * d * f * 2 / peaks.peaks("TPU v5 lite")[
        "hbm_bytes_per_s"]
    assert weights > 2 * 3 * 26_500e-9
    assert flops_lfm2.gmm_call(rows[0], d, f)[1] == 16 * (d + f) * 2
    steps = [{"a": 0.0, "b": 0.02, "prefill_s": 0.0, "decode_s": 0.01,
              "n_prefill_calls": 0, "n_decode_calls": 1, "prefilled": [],
              "decoded": [100] * 16, "routed": [["decode", rows]]}]
    rec = dict(record(), steps=steps)
    rec["trace"] = dict(rec["trace"], steps=steps, windows=[[0, 20 * MS]],
                        step_spans=[[0, 20 * MS]],
                        device=events(0, 3 * N_MOE, 26_500))
    assert 0 < read("cadc_gmm_roofline.lfm2", rec) < 100
