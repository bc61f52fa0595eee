"""shard_map TP-CADC: correctness vs the single-device oracle.

Needs >1 device, so the test body runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest process
keeps 1 device — see dryrun.py note about global flags).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import cadc
    from repro.parallel.tp_cadc import (segment_weights, tp_cadc_linear,
                                        tp_vconv_linear)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    B, D, N, XBAR = 8, 512, 128, 64          # S = 8 segments over 4 devices
    x = jax.random.normal(key, (B, D))
    w = jax.random.normal(jax.random.fold_in(key, 1), (D, N)) / 22.6
    w_seg = segment_weights(w, XBAR)

    # CADC: shard_map == oracle (fp32 wire exactly; bf16 wire within tol)
    y_ref = cadc.cadc_matmul(x, w, crossbar_size=XBAR, fn="relu")
    y_f32 = tp_cadc_linear(x, w_seg, mesh=mesh, fn="relu", wire_dtype=None)
    np.testing.assert_allclose(np.asarray(y_f32), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)

    y_bf16 = tp_cadc_linear(x, w_seg, mesh=mesh, fn="relu",
                            wire_dtype=jnp.bfloat16)
    rel = float(jnp.linalg.norm(y_bf16 - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.01, f"bf16 wire rel err {rel}"   # compression is cheap

    # vConv baseline == exact matmul
    y_v = tp_vconv_linear(x, w_seg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(y_v), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)

    # wire dtype: assert at the StableHLO level (program intent). The CPU
    # backend upcasts bf16 ARs to f32; TPU executes them natively in bf16,
    # halving ICI payload — which is what the audit measures on the target.
    import re
    def ar_dtypes(wire):
        f = jax.jit(lambda a, b: tp_cadc_linear(a, b, mesh=mesh, fn="relu",
                                                wire_dtype=wire))
        txt = f.lower(x, w_seg).as_text()
        return set(m[1] for m in re.findall(
            r'all_reduce.*?\\(tensor<([0-9x]+x)?(\\w+)>\\)\\s*->', txt, re.S))
    assert ar_dtypes(jnp.bfloat16) == {"bf16"}, ar_dtypes(jnp.bfloat16)
    assert ar_dtypes(None) == {"f32"}, ar_dtypes(None)
    print(f"AR wire dtypes ok; bf16 rel_err={rel:.2e}")
    print("TP_CADC_OK")
""")


@pytest.mark.slow
def test_tp_cadc_shardmap():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _BODY], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "TP_CADC_OK" in out.stdout, out.stdout + out.stderr
