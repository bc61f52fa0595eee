"""The harness as a whole: it refuses to run without an accelerator or
without the program, finds new cells, configurations, mixes and metrics by
name alone, and its comparison catches a wrong answer or token."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp

import run
import smoke
from conftest import BENCH

ROOT = BENCH.parent


def cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def bench_cmd(*cell):
    return [sys.executable, "bench/run.py", "--workload", *cell, "--seed",
            str(2**31 + 1), "--seconds", "1", "--trace", "0"]


def test_no_accelerator_no_result():
    p = subprocess.run(bench_cmd("resnet18-cifar10.fp32-b256"), cwd=ROOT,
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_no_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(bench_cmd("resnet18-cifar10.fp32-b256"), cwd=tmp_path,
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


NEW_METRIC = '''"""Batches completed in the window over the window."""


def read(rec):
    if rec["kind"] != "cnn":
        return None
    return rec["attempted"] / rec["seconds"]
'''

DRIVE = '''import json, sys, time
sys.path.insert(0, "bench")
import run
out = run.run_cell("resnet18-tiny.fp32-b4", 2**31 + 3, 1.0, False,
                   t_start=time.perf_counter(), require_chip=False)
print(json.dumps({"correct": out["correct"], "metrics": out["metrics"]}))
'''


def test_new_files_are_found_by_name(tmp_path):
    """A new configuration, mix and metric are files and BENCHMARK.json
    entries; the harness's own files stay as they are."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = smoke.resnet()
    cfg["name"] = "resnet18-tiny"
    (tmp_path / "bench/configs/resnet18-tiny.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/fp32-b4.json").write_text(json.dumps(
        smoke.cnn_mix("fp32-b256")))
    (tmp_path / "bench/metrics/batches_per_s.py").write_text(NEW_METRIC)
    bench["configs"].append({
        "name": "resnet18-tiny", "source": "https://arxiv.org/abs/1512.03385",
        "file": "bench/configs/resnet18-tiny.json", "reduced": ["width"],
        "why": "a test"})
    bench["workloads"].append({
        "name": "resnet18-tiny.fp32-b4", "config": "resnet18-tiny",
        "traffic": "fp32-b4", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({
        "name": "batches_per_s", "unit": "batches/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["resnet18-tiny.fp32-b4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path,
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"batches_per_s", "setup_s"}
    assert out["metrics"]["batches_per_s"]["value"] > 0
    for f in BENCH.rglob("*.py"):
        rel = f.relative_to(BENCH)
        assert (tmp_path / "bench" / rel).read_bytes() == f.read_bytes()


def run_small(cell, cfg, mix, seconds=2.0):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return run.run_loaded(bench, cell, cfg, mix, 2**31 + 21, seconds, False,
                          t_start=time.perf_counter(), require_chip=False)


def test_cnn_answer_altered_is_not_correct(monkeypatch):
    from repro.models.cnn import resnet18

    cell = smoke.cell("resnet18-cifar10", "fp32-b256")
    cfg, mix = smoke.resnet(), smoke.cnn_mix("fp32-b256")
    assert run_small(cell, cfg, mix)["correct"] is True
    apply = resnet18.apply

    def altered(*a, **k):
        logits, state = apply(*a, **k)
        return logits.at[0].set(logits[0, ::-1]), state

    monkeypatch.setattr(resnet18, "apply", altered)
    out = run_small(cell, cfg, mix)
    assert out["correct"] is False


def test_cnn_q8_answer_altered_is_not_correct(monkeypatch):
    from repro.models.cnn import resnet18

    cell = smoke.cell("resnet18-cifar10", "q8-b256")
    cfg, mix = smoke.resnet(), smoke.cnn_mix("q8-b256")
    assert run_small(cell, cfg, mix)["correct"] is True
    apply = resnet18.apply

    def altered(*a, **k):
        logits, state = apply(*a, **k)
        return logits.at[0].set(logits[0, ::-1]), state

    monkeypatch.setattr(resnet18, "apply", altered)
    out = run_small(cell, cfg, mix)
    assert out["correct"] is False
    assert out["checks"]["top1_off_pct"]["value"] == 25.0


def test_served_token_altered_is_not_correct(monkeypatch):
    from repro.serve import backends

    cell = smoke.cell("phi4-mini-cadc", "chat")
    cfg, mix = smoke.phi4(), smoke.serve_mix("chat")
    assert run_small(cell, cfg, mix)["correct"] is True
    decode = backends.PagedBackend.decode

    def altered(self, *a, **k):
        nxt, logits, caches = decode(self, *a, **k)
        worst = jnp.argmin(logits, axis=-1).astype(nxt.dtype)
        return nxt.at[0].set(worst[0]), logits, caches

    monkeypatch.setattr(backends.PagedBackend, "decode", altered)
    out = run_small(cell, cfg, mix)
    assert out["correct"] is False
