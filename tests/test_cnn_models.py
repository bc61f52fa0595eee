"""Smoke + behaviour tests for the paper's four CNN benchmarks."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import PAPER_424
from repro.core import adc as adc_lib
from repro.models.cnn import lenet5, resnet18, snn, vgg16
from repro.models.common import Ctx, LayerMode

KEY = jax.random.PRNGKey(0)


def _check(logits, n_cls, bs):
    assert logits.shape == (bs, n_cls)
    assert np.isfinite(np.asarray(logits)).all()


class TestForwardShapes:
    @pytest.mark.parametrize("impl", ["vconv", "cadc"])
    def test_lenet5(self, impl):
        params, state = lenet5.init(KEY)
        x = jax.random.normal(KEY, (2, 28, 28, 1))
        logits, _ = lenet5.apply(params, state, x,
                                 Ctx(LayerMode(impl=impl, crossbar_size=64)))
        _check(logits, 10, 2)

    @pytest.mark.parametrize("impl", ["vconv", "cadc"])
    def test_resnet18_reduced(self, impl):
        params, state = resnet18.init(KEY, num_classes=10, width=16)
        x = jax.random.normal(KEY, (2, 32, 32, 3))
        logits, new_state = resnet18.apply(
            params, state, x, Ctx(LayerMode(impl=impl, crossbar_size=64)),
            train=True,
        )
        _check(logits, 10, 2)
        # BN state updated in train mode
        assert not np.allclose(
            new_state["bn_stem"]["mean"], state["bn_stem"]["mean"]
        )

    @pytest.mark.parametrize("impl", ["vconv", "cadc"])
    def test_vgg16_reduced(self, impl):
        params, state = vgg16.init(KEY, num_classes=100, width_div=8)
        x = jax.random.normal(KEY, (2, 32, 32, 3))
        logits, _ = vgg16.apply(
            params, state, x, Ctx(LayerMode(impl=impl, crossbar_size=64)),
            train=False,
        )
        _check(logits, 100, 2)

    @pytest.mark.parametrize("impl", ["vconv", "cadc"])
    def test_snn(self, impl):
        params, state = snn.init(KEY, num_classes=11, width=8, hw=16)
        x = (jax.random.uniform(KEY, (2, 4, 16, 16, 2)) < 0.1).astype(jnp.float32)
        mode = LayerMode(impl=impl, crossbar_size=64,
                         fn="sublinear" if impl == "cadc" else "relu")
        logits, _ = snn.apply(params, state, x, Ctx(mode))
        _check(logits, 11, 2)

    def test_full_size_resnet18_param_count(self):
        """Full ResNet-18/CIFAR ~= 11.2M params."""
        params, _ = resnet18.init(KEY, num_classes=10, width=64)
        n = sum(p.size for p in jax.tree_util.tree_leaves(params))
        assert 10e6 < n < 12e6, n


class TestStatsCollection:
    def test_lenet_conv1_excluded_conv2_partitioned(self):
        """Paper: Conv-1 (5*5*1=25 rows) fits one 64x64 crossbar -> no psums;
        conv2 (5*5*6=150) partitions into 3 segments."""
        params, state = lenet5.init(KEY)
        ctx = Ctx(LayerMode(impl="cadc", crossbar_size=64, collect_stats=True))
        x = jax.random.normal(KEY, (2, 28, 28, 1))
        lenet5.apply(params, state, x, ctx)
        stats = ctx.stats_dict()
        assert "conv1" not in stats          # single crossbar, excluded
        assert "conv2" in stats
        assert int(stats["conv2"]["segments"]) == 3
        assert "fc1" in stats                # 400 -> 7 segments
        assert int(stats["fc1"]["segments"]) == 7

    def test_cadc_sparsity_high_vconv_low(self):
        params, state = resnet18.init(KEY, width=16)
        x = jax.random.normal(KEY, (2, 32, 32, 3))
        ctx_c = Ctx(LayerMode(impl="cadc", crossbar_size=64, collect_stats=True))
        resnet18.apply(params, state, x, ctx_c)
        ctx_v = Ctx(LayerMode(impl="vconv", crossbar_size=64, collect_stats=True))
        resnet18.apply(params, state, x, ctx_v)
        sc = np.mean([float(s["sparsity"]) for s in ctx_c.stats])
        sv = np.mean([float(s["sparsity"]) for s in ctx_v.stats])
        assert sc > 0.3, sc     # random init: ~half psums negative
        # vConv psums are rarely exactly zero (only all-zero padded-border
        # segments produce them), CADC must dominate by a wide margin.
        assert sv < 0.2, sv
        assert sc > sv + 0.25


class TestQuantizedAndNoisy:
    def test_424_quant_forward(self):
        params, state = lenet5.init(KEY)
        mode = LayerMode(impl="cadc", crossbar_size=64, quant=PAPER_424)
        x = jax.random.normal(KEY, (2, 28, 28, 1))
        logits, _ = lenet5.apply(params, state, x, Ctx(mode))
        _check(logits, 10, 2)

    def test_adc_noise_changes_logits_only_slightly(self):
        params, state = lenet5.init(KEY)
        base = LayerMode(impl="cadc", crossbar_size=64)
        noisy = LayerMode(impl="cadc", crossbar_size=64,
                          adc=adc_lib.AdcConfig(bits=5))
        x = jax.random.normal(KEY, (4, 28, 28, 1))
        l0, _ = lenet5.apply(params, state, x, Ctx(base))
        l1, _ = lenet5.apply(params, state, x, Ctx(noisy, jax.random.PRNGKey(1)))
        rel = float(jnp.linalg.norm(l1 - l0) / (jnp.linalg.norm(l0) + 1e-9))
        assert 0 < rel < 0.5, rel

    def test_snn_grads_flow_through_spikes(self):
        params, state = snn.init(KEY, num_classes=4, width=4, hw=8)
        x = (jax.random.uniform(KEY, (2, 3, 8, 8, 2)) < 0.5).astype(jnp.float32)

        def loss(p):
            logits, _ = snn.apply(p, state, x, Ctx(LayerMode()))
            return jnp.sum(logits)  # nonzero grad even at logits == 0

        g = jax.grad(loss)(params)
        for name in ["c1", "c2"]:  # surrogate grads reach the convs
            gn = float(jnp.abs(g[name]["w"]).sum())
            assert np.isfinite(gn) and gn > 0, name


class TestNamedScopes:
    """ResNet-18's parts run under named scopes, which reach the compiled
    program as op-name metadata (a benchmark attributes device time by
    them), and the psum statistics keep their labels."""

    SCOPES = ["stem/stem/", "stem/bn/", "stem/relu/", "s1b0/conv1/",
              "s1b0/bn1/", "s1b0/relu/", "s1b0/conv2/", "s1b0/bn2/",
              "s1b0/proj/", "s1b0/bnp/", "s1b0/residual/", "s3b1/conv2/",
              "head/fc/"]

    @pytest.mark.parametrize("q8", [False, True])
    def test_scopes_in_the_compiled_program(self, q8):
        params, state = resnet18.init(KEY, num_classes=10, width=8)
        mode = LayerMode(impl="cadc", crossbar_size=64, kernel="xla",
                         quant=PAPER_424 if q8 else LayerMode().quant,
                         q8_fused=q8)
        x = jax.random.normal(KEY, (2, 32, 32, 3))
        text = jax.jit(
            lambda p, s, x: resnet18.apply(p, s, x, Ctx(mode))[0]
        ).lower(params, state, x).compile().as_text()
        names = " ".join(re.findall(r'op_name="([^"]*)"', text))
        want = self.SCOPES + (["s1b0/conv1/quantize_act/",
                               "s1b0/conv1/quantize_weight/",
                               "head/fc/quantize_act/"] if q8 else [])
        for scope in want:
            assert "/" + scope in names, scope

    def test_stats_labels_unchanged(self):
        params, state = resnet18.init(KEY, num_classes=10, width=8)
        ctx = Ctx(LayerMode(impl="cadc", crossbar_size=64,
                            collect_stats=True))
        x = jax.random.normal(KEY, (2, 32, 32, 3))
        resnet18.apply(params, state, x, ctx)
        # the layers of more than one 64-row crossbar: at width 8 every
        # 3x3 conv of a block (72 rows or more), no 1x1 projection (at most
        # 32), not the stem (27) nor the classifier (64)
        want = [f"s{si}b{bi}.{layer}" for si in range(4) for bi in range(2)
                for layer in ("conv1", "conv2")]
        assert sorted(ctx.stats_dict()) == sorted(want)
