"""Fused int8-native q8 conv kernel: bit-exactness vs the sequential q8
oracle, straight-through gradients in the packed-gate and recompute
residual modes, and the end-to-end quantized model path (the paper's
4/2/4b ResNet-18 runs every conv through cadc_conv2d_q8, bit-exact against
the oracle on every impl)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv import im2col
from repro.kernels import ops, ref
from repro.kernels.cadc_conv import cadc_conv2d_q8_pallas

KEY = jax.random.PRNGKey(0)
TOL = 1e-4
XBARS = [64, 128, 256]
# Small maps, where a grid step holds several images (b, h, cin, cout,
# stride; the plan of each in tests/test_conv_kernel.py): 4x4 and 2x2 maps
# with Cin = 2 crossbars of 64, B = 6 (nb = 3 at 8x8) and the prime B = 5
# (nb = 1).
MULTI_IMAGE = [
    (4, 4, 128, 64, 1),
    (4, 2, 128, 32, 1),
    (6, 8, 128, 32, 1),
    (6, 8, 128, 32, 2),
    (4, 4, 128, 256, 2),
    (5, 8, 128, 32, 1),
]
# B = 8 over two Cout blocks, the outermost grid axis. Its gradients are
# checked in fp32 (tests/test_conv_kernel.py): on these integer codes they
# reach ~200, where the backward's fp32 rounding exceeds the absolute TOL.
COLS_OUTER = (8, 8, 8, 256, 1)


def _mk_q8(b, h, w, cin, cout, k, seed=0):
    kx, kw = jax.random.split(jax.random.fold_in(KEY, seed))
    x_q = jax.random.randint(kx, (b, h, w, cin), -7, 8, jnp.int8)
    w_c = jax.random.randint(kw, (k, k, cin, cout), -1, 2, jnp.int8)
    return x_q, w_c, jnp.float32(0.731)


class TestQ8ConvBitExact:
    @pytest.mark.parametrize("xbar", XBARS)
    def test_matches_oracle_bitexact(self, xbar):
        # D = 3*3*20 = 180: ragged vs 64/128, single-segment vs 256.
        x_q, w_c, sc = _mk_q8(2, 10, 10, 20, 24, 3, seed=xbar)
        got = cadc_conv2d_q8_pallas(x_q, w_c, sc, crossbar_size=xbar,
                                    fn="relu", interpret=True)
        want = ref.cadc_conv2d_q8_ref(x_q, w_c, sc, crossbar_size=xbar,
                                      fn="relu")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("stride", [(1, 1), (2, 2)])
    @pytest.mark.parametrize("padding", ["SAME", "VALID"])
    def test_stride_padding_sweep(self, stride, padding):
        x_q, w_c, sc = _mk_q8(1, 9, 9, 16, 12, 3, seed=7)
        got = cadc_conv2d_q8_pallas(x_q, w_c, sc, crossbar_size=64,
                                    fn="relu", stride=stride,
                                    padding=padding, interpret=True)
        want = ref.cadc_conv2d_q8_ref(x_q, w_c, sc, crossbar_size=64,
                                      fn="relu", stride=stride,
                                      padding=padding)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("b,h,cin,cout,s", MULTI_IMAGE + [COLS_OUTER])
    def test_multi_image_bitexact(self, b, h, cin, cout, s):
        x_q, w_c, sc = _mk_q8(b, h, h, cin, cout, 3, seed=b * h + s)
        got = cadc_conv2d_q8_pallas(x_q, w_c, sc, crossbar_size=64,
                                    fn="relu", stride=(s, s), interpret=True)
        want = ref.cadc_conv2d_q8_ref(x_q, w_c, sc, crossbar_size=64,
                                      fn="relu", stride=(s, s))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_ops_dispatch_xla_is_oracle(self):
        """The xla impl IS the oracle — dispatch must be numerics-
        transparent (what the end-to-end model parity relies on)."""
        x_q, w_c, sc = _mk_q8(1, 8, 8, 20, 8, 3, seed=9)
        a = ops.cadc_conv2d_q8(x_q, w_c, sc, crossbar_size=64,
                               impl="interpret")
        b = ops.cadc_conv2d_q8(x_q, w_c, sc, crossbar_size=64, impl="xla")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestQ8ConvGrads:
    """STE grads (float arrays holding integer values) vs a float oracle
    with the exact per-segment accumulation — packed and recompute
    residual modes must both hold parity <= 1e-4."""

    @staticmethod
    def _float_oracle(x, w, s, *, xbar, stride=(1, 1), padding="SAME"):
        # f'(0) = 0 convention (matches the saved relu bitmask; exact-zero
        # psums are COMMON with integer data).
        relu0 = lambda p: jnp.where(p > 0, p, 0.0)
        k1, k2, cin, cout = w.shape
        d = k1 * k2 * cin
        n_seg = -(-d // xbar)
        pad = n_seg * xbar - d
        patches = im2col(x, (k1, k2), stride=stride, padding=padding)
        pp = jnp.pad(patches, ((0, 0),) * 3 + ((0, pad),))
        w2 = jnp.pad(w.reshape(d, cout), ((0, pad), (0, 0)))
        acc = 0.0
        for i in range(n_seg):
            acc = acc + relu0(
                s * (pp[..., i * xbar:(i + 1) * xbar]
                     @ w2[i * xbar:(i + 1) * xbar]))
        return acc

    def _check_parity(self, x_q, w_c, sc, *, xbar, save_gate,
                      stride=(1, 1), block_n=128):
        xf, wf = x_q.astype(jnp.float32), w_c.astype(jnp.float32)

        def pallas_op(a, b, s):
            return cadc_conv2d_q8_pallas(
                a, b, s, crossbar_size=xbar, fn="relu", stride=stride,
                block_n=block_n, interpret=True, save_gate=save_gate)

        def oracle(a, b, s):
            return self._float_oracle(a, b, s, xbar=xbar, stride=stride)

        y = pallas_op(xf, wf, sc)
        r = jax.random.normal(jax.random.fold_in(KEY, 99), y.shape)
        gx, gw, gs = jax.grad(
            lambda *a: jnp.vdot(pallas_op(*a), r), argnums=(0, 1, 2)
        )(xf, wf, sc)
        hx, hw, hs = jax.grad(
            lambda *a: jnp.vdot(oracle(*a), r), argnums=(0, 1, 2)
        )(xf, wf, sc)
        assert float(jnp.max(jnp.abs(gx - hx))) <= TOL
        assert float(jnp.max(jnp.abs(gw - hw))) <= TOL
        assert abs(float(gs - hs)) <= TOL * max(1.0, abs(float(hs)))

    @pytest.mark.parametrize("xbar", XBARS)
    @pytest.mark.parametrize("save_gate", ["packed", "recompute"])
    def test_parity(self, xbar, save_gate):
        # cout=32 keeps bn % 32 == 0 so "packed" is genuinely packed.
        x_q, w_c, sc = _mk_q8(1, 8, 8, 20, 32, 3, seed=xbar + 1)
        self._check_parity(x_q, w_c, sc, xbar=xbar, save_gate=save_gate,
                           block_n=32)

    @pytest.mark.parametrize("save_gate", ["packed", "bytes"])
    @pytest.mark.parametrize("b,h,cin,cout,s", MULTI_IMAGE)
    def test_multi_image_parity(self, b, h, cin, cout, s, save_gate):
        x_q, w_c, sc = _mk_q8(b, h, h, cin, cout, 3, seed=b * h + s + 1)
        self._check_parity(x_q, w_c, sc, xbar=64, save_gate=save_gate,
                           stride=(s, s))

    def test_int_primals_get_float0_scale_grad_flows(self):
        x_q, w_c, sc = _mk_q8(1, 6, 6, 16, 8, 3, seed=31)
        r = None

        def loss(s):
            return jnp.sum(cadc_conv2d_q8_pallas(
                x_q, w_c, s, crossbar_size=64, fn="relu", interpret=True))

        g = jax.grad(loss)(sc)
        h = jax.grad(lambda s: jnp.sum(ref.cadc_conv2d_q8_ref(
            x_q, w_c, s, crossbar_size=64, fn="relu")))(sc)
        assert abs(float(g - h)) <= TOL * max(1.0, abs(float(h)))


class TestQ8EndToEnd:
    def test_resnet18_q8_fused_bitexact_vs_oracle(self):
        """Paper's quantized ResNet-18 forward end-to-end through
        cadc_conv2d_q8 / cadc_matmul_q8 (interpret) == the same network on
        the oracle dispatch (xla) bit-exactly."""
        from repro.core.quant import PAPER_424
        from repro.models.cnn import resnet18
        from repro.models.common import Ctx, LayerMode

        key = jax.random.PRNGKey(0)
        params, state = resnet18.init(key, num_classes=10, in_ch=3, width=8)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 8, 3))
        logits = {}
        for kern in ["xla", "interpret"]:
            mode = LayerMode(impl="cadc", crossbar_size=64, fn="relu",
                             quant=PAPER_424, kernel=kern, q8_fused=True)
            out, _ = resnet18.apply(params, state, x, Ctx(mode), train=False)
            logits[kern] = np.asarray(out)
        np.testing.assert_array_equal(logits["xla"], logits["interpret"])

    def test_q8_fused_blocks_gradients(self):
        """q8_fused is inference-only: jax.grad through a q8_fused layer is
        EXACTLY zero (stop_gradient), not a spurious scale-direction
        partial — training must use the fake-quant STE path instead."""
        from repro.core.quant import PAPER_424
        from repro.models import common as cm
        from repro.models.common import Ctx, LayerMode

        key = jax.random.PRNGKey(2)
        p = cm.conv_init(key, 3, 3, 8, 8)
        x = jax.random.normal(jax.random.fold_in(key, 1), (1, 6, 6, 8))
        mode = LayerMode(impl="cadc", crossbar_size=32, fn="relu",
                         quant=PAPER_424, kernel="interpret", q8_fused=True)

        def loss(params, xin):
            return jnp.sum(cm.conv_forward(params, xin, Ctx(mode)))

        gw, gx = jax.grad(loss, argnums=(0, 1))(p, x)
        assert float(jnp.max(jnp.abs(gw["w"]))) == 0.0
        assert float(jnp.max(jnp.abs(gx))) == 0.0

    def test_vgg16_q8_fused_bitexact_vs_oracle(self):
        from repro.core.quant import PAPER_424
        from repro.models.cnn import vgg16
        from repro.models.common import Ctx, LayerMode

        key = jax.random.PRNGKey(1)
        params, state = vgg16.init(key, num_classes=10, width_div=16)
        x = jax.random.normal(jax.random.fold_in(key, 1), (1, 32, 32, 3))
        logits = {}
        for kern in ["xla", "interpret"]:
            mode = LayerMode(impl="cadc", crossbar_size=64, fn="relu",
                             quant=PAPER_424, kernel=kern, q8_fused=True)
            out, _ = vgg16.apply(params, state, x, Ctx(mode), train=False)
            logits[kern] = np.asarray(out)
        np.testing.assert_array_equal(logits["xla"], logits["interpret"])
