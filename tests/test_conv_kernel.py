"""Fused CADC conv Pallas kernel vs the im2col oracle: shape/dtype sweep +
hypothesis property tests (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.conv import cadc_conv2d, vconv_conv2d
from repro.kernels import ops
from repro.kernels.cadc_conv import (FMAP_VMEM_BUDGET, ROW_TARGET,
                                     ConvPlan, cadc_conv2d_pallas,
                                     conv_block_plan, _segment_taps)

KEY = jax.random.PRNGKey(0)


def _mk(b, h, w, cin, cout, k, dtype=jnp.float32):
    x = jax.random.normal(KEY, (b, h, w, cin), dtype)
    wt = jax.random.normal(jax.random.fold_in(KEY, 1), (k, k, cin, cout),
                           dtype) / (k * np.sqrt(cin))
    return x, wt


SWEEP = [
    # b, h, w, cin, cout, k, stride, xbar, fn
    (2, 16, 16, 32, 64, 3, 1, 64, "relu"),
    (2, 16, 16, 32, 64, 3, 2, 64, "relu"),
    (1, 8, 8, 16, 24, 5, 1, 32, "tanh"),
    (2, 12, 12, 8, 16, 3, 1, 128, "sublinear"),
    (1, 10, 10, 6, 8, 1, 1, 4, "relu"),          # 1x1 conv
    (2, 9, 9, 20, 12, 3, 1, 64, "supralinear"),  # segment spans taps
]
# Small maps, where a grid step holds several images: b, h, cin, cout,
# stride, the images a step (nb) and whether the Cout blocks are the
# outermost grid axis. Cin = 2 crossbars of 64; B = 6 at 8x8 takes its
# divisor 3; the prime B = 5 at 8x8 keeps one image a step; B = 8 over
# two Cout blocks fetches fewer bytes with the Cout blocks outermost.
MULTI_IMAGE = [
    (4, 4, 128, 64, 1, 4, False),
    (4, 2, 128, 32, 1, 4, False),
    (6, 8, 128, 32, 1, 3, False),
    (6, 8, 128, 32, 2, 6, False),
    (4, 4, 128, 256, 2, 4, False),
    (5, 8, 128, 32, 1, 1, False),
    (8, 8, 8, 256, 1, 4, True),
]
SWEEP += [(b, h, h, cin, cout, 3, s, 64, "relu")
          for b, h, cin, cout, s, _, _ in MULTI_IMAGE]


@pytest.mark.parametrize("b,h,w,cin,cout,k,s,xbar,fn", SWEEP)
def test_fused_conv_matches_oracle(b, h, w, cin, cout, k, s, xbar, fn):
    x, wt = _mk(b, h, w, cin, cout, k)
    ref = cadc_conv2d(x, wt, crossbar_size=xbar, fn=fn, stride=(s, s),
                      padding="SAME")
    out = cadc_conv2d_pallas(x, wt, crossbar_size=xbar, fn=fn, stride=(s, s),
                             padding="SAME", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,h,cin,cout,s,nb,cols", MULTI_IMAGE)
def test_multi_image_plan(b, h, cin, cout, s, nb, cols):
    plan = conv_block_plan((b, h, h, cin), (3, 3, cin, cout), stride=(s, s))
    assert (plan.nb, plan.cols_outer) == (nb, cols)


@pytest.mark.parametrize("save_gate", ["packed", "bytes"])
@pytest.mark.parametrize("b,h,cin,cout,s,nb,cols", MULTI_IMAGE)
def test_multi_image_grads(b, h, cin, cout, s, nb, cols, save_gate):
    """The gate block of a step covers its nb images: gradients through
    the saved gate match XLA autodiff of the oracle."""
    x, wt = _mk(b, h, h, cin, cout, 3)
    r = jax.random.normal(jax.random.fold_in(KEY, 99),
                          cadc_conv2d(x, wt, crossbar_size=64,
                                      stride=(s, s)).shape)

    def grads(conv):
        return jax.grad(lambda a, w: jnp.vdot(conv(a, w), r),
                        argnums=(0, 1))(x, wt)

    got = grads(lambda a, w: cadc_conv2d_pallas(
        a, w, crossbar_size=64, fn="relu", stride=(s, s), interpret=True,
        save_gate=save_gate))
    want = grads(lambda a, w: cadc_conv2d(a, w, crossbar_size=64, fn="relu",
                                          stride=(s, s)))
    for g, h_ in zip(got, want):
        assert float(jnp.max(jnp.abs(g - h_))) <= 1e-4


# ResNet-18 (CIFAR, width 64, crossbar 64) at batch 256: (H, Cin, Cout, k,
# stride) of each distinct conv, the calls of one forward that run it, and
# its block plan (nb, bh, bn, rows a dot, grid steps, Cout blocks outermost)
RESNET18_B256 = [
    ((32, 3, 64, 3, 1), 1, (1, 8, 64, 256, 1024, False)),      # stem
    ((32, 64, 64, 3, 1), 4, (1, 8, 64, 256, 1024, False)),     # s0
    ((32, 64, 128, 3, 2), 1, (2, 8, 128, 256, 256, False)),    # s1b0 conv1
    ((32, 64, 128, 1, 2), 1, (2, 8, 128, 256, 256, False)),    # s1b0 proj
    ((16, 128, 128, 3, 1), 3, (2, 8, 128, 256, 256, False)),   # s1
    ((16, 128, 256, 3, 2), 1, (4, 8, 128, 256, 128, True)),    # s2b0 conv1
    ((16, 128, 256, 1, 2), 1, (4, 8, 128, 256, 128, False)),   # s2b0 proj
    ((8, 256, 256, 3, 1), 3, (4, 8, 128, 256, 128, True)),     # s2
    ((8, 256, 512, 3, 2), 1, (16, 4, 128, 256, 64, False)),    # s3b0 conv1
    ((8, 256, 512, 1, 2), 1, (16, 4, 128, 256, 64, False)),    # s3b0 proj
    ((4, 512, 512, 3, 1), 3, (16, 4, 128, 256, 64, True)),     # s3
]


@pytest.mark.parametrize("itemsize", [4, 1])
def test_resnet18_block_plan(itemsize):
    """The plan of the 20 convs of a batch-256 forward: every dot streams
    ROW_TARGET rows, the stem and stage 0 keep one image a step and the
    batch outermost (their kernel is the one-image kernel), the step's
    images fit the budget."""
    assert sum(n for _, n, _ in RESNET18_B256) == 20
    for (h, cin, cout, k, s), _, want in RESNET18_B256:
        plan = conv_block_plan((256, h, h, cin), (k, k, cin, cout),
                               stride=(s, s), itemsize=itemsize)
        assert plan == ConvPlan(*want), (h, cin, cout, k, s)
        assert plan.rows >= ROW_TARGET
        hq = -(-h // s) + (k - 1) // s
        assert plan.nb * s * s * hq * hq * cin * itemsize <= FMAP_VMEM_BUDGET


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    x, wt = _mk(2, 12, 12, 16, 32, 3, dtype)
    ref = cadc_conv2d(x.astype(jnp.float32), wt.astype(jnp.float32),
                      crossbar_size=64, fn="relu")
    out = cadc_conv2d_pallas(x, wt, crossbar_size=64, fn="relu",
                             interpret=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


def test_valid_padding():
    x, wt = _mk(1, 12, 12, 8, 8, 3)
    ref = cadc_conv2d(x, wt, crossbar_size=32, fn="relu", padding="VALID")
    out = cadc_conv2d_pallas(x, wt, crossbar_size=32, fn="relu",
                             padding="VALID", interpret=True)
    assert out.shape == ref.shape == (1, 10, 10, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_identity_fn_equals_lax_conv():
    """f=identity -> fused kernel == plain convolution (vConv exactness)."""
    x, wt = _mk(2, 10, 10, 12, 16, 3)
    out = cadc_conv2d_pallas(x, wt, crossbar_size=32, fn="identity",
                             interpret=True)
    direct = jax.lax.conv_general_dilated(
        x, wt, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               rtol=1e-4, atol=1e-4)


def test_ops_wrapper_fallback():
    """ops.cadc_conv2d: interpret path and the xla fallback agree."""
    x, wt = _mk(1, 8, 8, 8, 8, 3)
    a = ops.cadc_conv2d(x, wt, crossbar_size=32, impl="interpret")
    b = ops.cadc_conv2d(x, wt, crossbar_size=32, impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


class TestSegmentTapsEdgeCases:
    """Fused kernel vs the segmented matmul oracle over im2col patches —
    the exact reduction the conv is defined as — at the segmentation
    table's corner cases."""

    @staticmethod
    def _fused_vs_im2col_oracle(b, h, w_, cin, cout, k, xbar, *,
                                stride=(1, 1), padding="SAME"):
        from repro.core.conv import im2col
        from repro.kernels.ref import cadc_matmul_ref

        x, wt = _mk(b, h, w_, cin, cout, k)
        out = cadc_conv2d_pallas(x, wt, crossbar_size=xbar, fn="relu",
                                 stride=stride, padding=padding,
                                 interpret=True)
        patches = im2col(x, (k, k), stride=stride, padding=padding)
        want = cadc_matmul_ref(patches, wt.reshape(k * k * cin, cout),
                               crossbar_size=xbar, fn="relu")
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_crossbar_smaller_than_cin(self):
        """xbar < Cin: several segments live INSIDE one spatial tap."""
        self._fused_vs_im2col_oracle(2, 8, 8, 48, 16, 3, xbar=16)

    def test_crossbar_not_dividing_d(self):
        """D = 3*3*20 = 180, xbar = 64: ragged last segment (180 = 2*64
        + 52) with tap-spanning interior segments."""
        self._fused_vs_im2col_oracle(2, 9, 9, 20, 12, 3, xbar=64)

    def test_stride2_valid_padding(self):
        """(2,2) stride under VALID padding — the in-register stride
        slicing composes with the unpadded row offsets."""
        self._fused_vs_im2col_oracle(1, 11, 11, 24, 8, 3, xbar=32,
                                     stride=(2, 2), padding="VALID")

    def test_stride2_valid_ragged_all_at_once(self):
        """Every edge at once: xbar < Cin, non-dividing D, stride 2,
        VALID."""
        self._fused_vs_im2col_oracle(2, 10, 10, 40, 8, 3, xbar=48,
                                     stride=(2, 2), padding="VALID")


class TestConvVmemBudget:
    """ops.cadc_conv2d's fused-vs-fallback routing (the VMEM estimate must
    follow the REAL padding, and empty batches must not launch Pallas)."""

    def test_estimate_uses_real_padding(self):
        from repro.kernels.ops import _conv_fmap_vmem_bytes

        x_shape, w_shape = (2, 16, 16, 8), (3, 3, 8, 4)
        same = _conv_fmap_vmem_bytes(x_shape, w_shape, "SAME")
        valid = _conv_fmap_vmem_bytes(x_shape, w_shape, "VALID")
        explicit = _conv_fmap_vmem_bytes(x_shape, w_shape, ((2, 2), (0, 0)))
        assert same == 18 * 18 * 8 * 4
        assert valid == 16 * 16 * 8 * 4  # no halo — old formula said 19*19
        assert explicit == 20 * 16 * 8 * 4
        # itemsize scales (int8 fmaps are 4x denser)
        assert _conv_fmap_vmem_bytes(x_shape, w_shape, "VALID", 1) == valid // 4

    def test_1x1_same_pads_nothing(self):
        from repro.kernels.ops import _conv_fmap_vmem_bytes

        assert _conv_fmap_vmem_bytes((1, 8, 8, 16), (1, 1, 16, 4), "SAME") \
            == 8 * 8 * 16 * 4

    def test_fallback_boundary(self, monkeypatch):
        """Just-at-budget runs fused; one byte under falls back to XLA."""
        import repro.kernels.cadc_conv as ck
        from repro.kernels.ops import _conv_fmap_vmem_bytes

        x, wt = _mk(1, 8, 8, 8, 8, 3)
        need = _conv_fmap_vmem_bytes(x.shape, wt.shape, "SAME")
        calls = []
        real = ck.cadc_conv2d_pallas
        monkeypatch.setattr(
            ck, "cadc_conv2d_pallas",
            lambda *a, **k: calls.append(1) or real(*a, **k))
        y_fused = ops.cadc_conv2d(x, wt, crossbar_size=32, impl="interpret",
                                  vmem_budget_bytes=need)
        assert calls == [1]
        y_fallback = ops.cadc_conv2d(x, wt, crossbar_size=32,
                                     impl="interpret",
                                     vmem_budget_bytes=need - 1)
        assert calls == [1]  # not called again -> xla path
        np.testing.assert_allclose(np.asarray(y_fused),
                                   np.asarray(y_fallback),
                                   rtol=1e-4, atol=1e-4)

    def test_empty_batch_falls_back(self, monkeypatch):
        """B = 0 must not reach the Pallas launch (zero-size grid) and
        still return the right shape."""
        import repro.kernels.cadc_conv as ck

        x, wt = _mk(1, 8, 8, 8, 8, 3)
        x0 = x[:0]
        monkeypatch.setattr(
            ck, "cadc_conv2d_pallas",
            lambda *a, **k: pytest.fail("pallas launched for empty batch"))
        y = ops.cadc_conv2d(x0, wt, crossbar_size=32, impl="interpret")
        assert y.shape == (0, 8, 8, 8)


class TestSegmentTaps:
    """The static segmentation table is the kernel's correctness core."""

    @given(k=st.sampled_from([1, 3, 5]), c=st.integers(1, 64),
           xbar=st.sampled_from([4, 32, 64, 256]))
    @settings(max_examples=40, deadline=None)
    def test_partition_covers_exactly(self, k, c, xbar):
        segs = _segment_taps(k, k, c, xbar)
        d = k * k * c
        assert len(segs) == -(-d // xbar)
        covered = []
        for s, taps in enumerate(segs):
            for (i, j, c_lo, c_sz, d_off) in taps:
                t = i * k + j
                start = t * c + c_lo
                covered.extend(range(start, start + c_sz))
                # d_off consistency: position within the segment window
                assert start - (s * xbar) == d_off
        assert covered == list(range(d))  # exact cover, in order, no overlap

    @given(c=st.integers(4, 48), xbar=st.sampled_from([8, 16, 64]))
    @settings(max_examples=20, deadline=None)
    def test_psum_sparsity_invariant(self, c, xbar):
        """Property: CADC(relu) output >= 0 when every segment psum is
        clamped — and equals vConv when f=identity."""
        x = jax.random.normal(jax.random.PRNGKey(c), (1, 6, 6, c))
        wt = jax.random.normal(jax.random.PRNGKey(c + 1), (3, 3, c, 8)) * 0.1
        y_id = cadc_conv2d_pallas(x, wt, crossbar_size=xbar, fn="identity",
                                  interpret=True)
        y_ref = vconv_conv2d(x, wt, crossbar_size=xbar)
        np.testing.assert_allclose(np.asarray(y_id), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        y_relu = cadc_conv2d_pallas(x, wt, crossbar_size=xbar, fn="relu",
                                    interpret=True)
        assert float(jnp.min(y_relu)) >= 0.0
