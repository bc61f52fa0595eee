"""End-to-end launch drivers: training with checkpoint-restart (fault
tolerance) and batched decode serving — the production path on the local
mesh."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, cache_dir, timeout=560):
    # the drivers turn the persistent compile cache on; keep their entries
    # out of the checkout
    env = dict(os.environ, PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    return subprocess.run([sys.executable, "-m"] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_driver_ckpt_restart(tmp_path):
    ck = str(tmp_path / "ck")
    common = ["repro.launch.train", "--arch", "gemma3_1b", "--smoke",
              "--cadc", "--batch", "2", "--seq", "32", "--ckpt-dir", ck,
              "--ckpt-every", "4", "--log-every", "2"]
    r1 = _run(common + ["--steps", "8"], tmp_path / "jax_cache")
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert "ckpt ->" in r1.stdout
    # restart: must resume from step 8, not step 0
    r2 = _run(common + ["--steps", "12"], tmp_path / "jax_cache")
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "restored step 8" in r2.stdout, r2.stdout
    # steps 0..7 ran in run 1 and must NOT re-run after restore
    assert "step     0" not in r2.stdout, r2.stdout
    # checkpoints GC'd to keep-k
    npz = [f for f in os.listdir(ck) if f.endswith(".npz")]
    assert 0 < len(npz) <= 3


@pytest.mark.slow
def test_serve_driver_decodes(tmp_path):
    r = _run(["repro.launch.serve", "--arch", "gemma3_1b", "--smoke",
              "--cadc", "--batch", "2", "--prompt-len", "4", "--gen", "4"],
             tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tok/s" in r.stdout


@pytest.mark.slow
def test_serve_rejects_encoder(tmp_path):
    r = _run(["repro.launch.serve", "--arch", "hubert_xlarge", "--smoke"],
             tmp_path)
    assert r.returncode != 0
    assert "encoder-only" in (r.stdout + r.stderr)


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """chip_smoke.py is the chip's proof: on any other backend it must fail
    and print no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    script = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    r = subprocess.run([sys.executable, script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout, r.stdout
    assert "no TPU found" in r.stderr, r.stderr
