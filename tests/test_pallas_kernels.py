"""Pallas kernel parity tests (interpret mode on the CPU mesh).

Reference analog: the cuDNN-vs-generic parity tests (CuDNNGradientChecks,
TestConvolution) — run the same op with and without the accelerated helper
and assert allclose. Kernels run in Pallas interpret mode off-TPU, so these
tests validate kernel logic; Mosaic compilation is exercised on real TPU.
"""

import functools
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import get_op
from deeplearning4j_tpu.ops.attention import dot_product_attention
from deeplearning4j_tpu.ops.pallas import flash_attention, fused_lstm_layer
from deeplearning4j_tpu.ops.recurrent import lstm_layer

# the module: the package's attribute of that name is the function
flash_module = importlib.import_module(
    "deeplearning4j_tpu.ops.pallas.flash_attention")


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla(self, rng, causal):
        B, H, T, D = 2, 2, 256, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        out = flash_attention(q, k, v, causal=causal)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_rectangular_blocks(self, rng):
        B, H, T, D = 1, 1, 384, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        out = flash_attention(q, k, v, block_q=128, block_k=256)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_gradients_flow(self, rng):
        B, H, T, D = 1, 2, 128, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))

        g1 = jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
        g2 = jax.grad(lambda q: dot_product_attention(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("D", [64, 128])
    def test_key_padding_mask_matches_xla(self, rng, causal, D):
        """r4: the kernel serves DL4J-style key-padding masks ([B,1,1,Tk]
        from the layer tier) — the shape every padded-batch BERT/encoder
        workload produces — instead of falling back to the XLA lowering."""
        B, H, T = 3, 2, 256
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        m = np.ones((B, T), np.float32)
        m[0, T // 2:] = 0          # half-padded example
        m[1, 10:] = 0              # nearly-all-padded example
        mask = jnp.asarray(m)[:, None, None, :]
        out = flash_attention(q, k, v, mask=mask, causal=causal)
        ref = dot_product_attention(q, k, v, mask=mask, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_fully_masked_rows_output_zero(self, rng):
        """A fully-masked example outputs exact zeros (the XLA lowering
        degrades to a uniform softmax over -inf logits there; zero is the
        behavior DL4J's downstream feed_forward_mask expects)."""
        B, H, T, D = 2, 1, 128, 64
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        m = np.ones((B, T), np.float32)
        m[1, :] = 0
        out = flash_attention(q, q, q, mask=jnp.asarray(m))
        assert float(jnp.abs(out[1]).max()) == 0.0
        assert bool(jnp.all(jnp.isfinite(out)))
        # and the backward stays finite through the masked example
        g = jax.grad(lambda q: flash_attention(q, q, q,
                                               mask=jnp.asarray(m)).sum())(q)
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.abs(g[1]).max()) == 0.0

    def test_masked_gradients_match_xla(self, rng):
        B, H, T, D = 2, 2, 256, 64
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        m = np.ones((B, T), np.float32)
        m[:, T // 3:] = 0
        mask = jnp.asarray(m)[:, None, None, :]
        for arg in range(3):
            gf = jax.grad(lambda *a: flash_attention(
                *a, mask=mask).sum(), argnums=arg)(q, k, v)
            gr = jax.grad(lambda *a: dot_product_attention(
                *a, mask=mask).sum(), argnums=arg)(q, k, v)
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       rtol=2e-3, atol=2e-3)

    def test_head_dim_64_matches_xla(self, rng):
        """r4: D=64 (BERT-base geometry, BASELINE config #4) runs natively —
        no padding; the QK^T contraction half-fills the MXU K dim but P@V
        stays full-rate."""
        B, H, T, D = 2, 4, 512, 64
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        out = flash_attention(q, k, v)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        g1 = jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
        g2 = jax.grad(lambda q: dot_product_attention(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-3, atol=2e-3)

    def test_registry_selection(self, rng, monkeypatch):
        op = get_op("dot_product_attention")
        # long aligned unmasked sequence -> pallas impl selected
        q = jnp.zeros((1, 1, 2048, 128), jnp.float32)
        assert op.select(q, q, q).platform == "pallas"
        # BERT-class geometry (head_dim 64) qualifies at long T (r4)
        qb = jnp.zeros((2, 12, 2048, 64), jnp.float32)
        assert op.select(qb, qb, qb).platform == "pallas"
        # key-padding mask (layer-tier [B,1,1,Tk]) rides the kernel (r4)
        km = jnp.ones((2, 1, 1, 2048))
        assert op.select(qb, qb, qb, mask=km).platform == "pallas"
        # T=512/1024: measured demotion (r4, BASELINE.md — XLA wins below
        # T=2048; the r1-r3 threshold of 512 was selecting losing regimes)
        q5 = jnp.zeros((8, 12, 512, 64), jnp.float32)
        assert op.select(q5, q5, q5).platform == "xla"
        # ...but FORCE_PALLAS can still exercise the kernel there (perf
        # heuristic, not a structural limit)
        from deeplearning4j_tpu.common.env import env

        monkeypatch.setattr(env, "force_pallas", True)
        assert op.select(q5, q5, q5).platform == "pallas"
        monkeypatch.setattr(env, "force_pallas", False)
        # short sequence -> xla
        q2 = jnp.zeros((1, 1, 64, 128), jnp.float32)
        assert op.select(q2, q2, q2).platform == "xla"
        # general [Tq,Tk]-varying mask -> structurally xla
        assert op.select(q, q, q,
                         mask=jnp.ones((1, 1, 2048, 2048))).platform == "xla"
        # kill switch (the remove-deeplearning4j-cuda-from-classpath analog)
        monkeypatch.setattr(env, "disable_pallas", True)
        assert op.select(q, q, q).platform == "xla"


class TestFusedLSTM:
    def test_matches_scan(self, rng):
        B, T, F, H = 8, 12, 16, 128
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        c0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * 0.1)
        b = jnp.asarray(rng.normal(size=(4 * H,)).astype(np.float32) * 0.1)

        out_f, (hT_f, cT_f) = fused_lstm_layer(x, h0, c0, W, R, b)
        out_r, (hT_r, cT_r) = lstm_layer(x, h0, c0, W, R, b)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(hT_f), np.asarray(hT_r),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cT_f), np.asarray(cT_r),
                                   rtol=2e-4, atol=2e-5)

    def test_reverse(self, rng):
        B, T, F, H = 8, 6, 8, 128
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        c0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * 0.1)
        b = jnp.zeros((4 * H,))
        out_f, _ = fused_lstm_layer(x, h0, c0, W, R, b, reverse=True)
        out_r, _ = lstm_layer(x, h0, c0, W, R, b, reverse=True)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-5)

    def test_registry_predicate(self):
        op = get_op("lstm_layer")
        x = jnp.zeros((8, 4, 16))
        h0 = c0 = jnp.zeros((8, 128))
        W = jnp.zeros((16, 512))
        R = jnp.zeros((128, 512))
        b = jnp.zeros((512,))
        assert op.select(x, h0, c0, W, R, b).platform == "pallas"
        # peephole (GravesLSTM) is fused in-kernel too (r2)
        assert op.select(x, h0, c0, W, R, b,
                         peephole=jnp.zeros(384)).platform == "pallas"
        # unaligned hidden size: r3 runs it on the kernel via zero-padding
        R2 = jnp.zeros((100, 400))
        assert op.select(x, jnp.zeros((8, 100)), jnp.zeros((8, 100)),
                         jnp.zeros((16, 400)), R2,
                         jnp.zeros(400)).platform == "pallas"
        # unaligned BATCH (sublane) -> xla
        x7 = jnp.zeros((7, 4, 16))
        assert op.select(x7, jnp.zeros((7, 128)), jnp.zeros((7, 128)),
                         W, R, b).platform == "xla"


class TestFusedLSTMTiled:
    """r2: hidden-tiled recurrence (VMEM-budget tiles) + fused peepholes."""

    def test_peephole_matches_scan(self, rng):
        B, T, F, H = 8, 10, 12, 128
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        c0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * 0.1)
        b = jnp.asarray(rng.normal(size=(4 * H,)).astype(np.float32) * 0.1)
        p = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * 0.1)
        of, (hf, cf) = fused_lstm_layer(x, h0, c0, W, R, b, peephole=p)
        orr, (hr, cr) = lstm_layer(x, h0, c0, W, R, b, peephole=p)
        np.testing.assert_allclose(np.asarray(of), np.asarray(orr),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cf), np.asarray(cr),
                                   rtol=2e-4, atol=2e-5)

    def test_hidden_tiling_matches_untiled(self, rng, monkeypatch):
        """Force Hb < H so the double-buffered multi-tile path runs."""
        import deeplearning4j_tpu.ops.pallas.fused_lstm as fl

        monkeypatch.setattr(fl, "lstm_tile", lambda *a, **k: 128)
        B, T, F, H = 4, 6, 8, 256  # -> 2 hidden tiles
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        c0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * 0.1)
        b = jnp.zeros((4 * H,))
        p = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * 0.1)
        of, (hf, cf) = fl.fused_lstm_layer(x, h0, c0, W, R, b, peephole=p)
        orr, (hr, cr) = lstm_layer(x, h0, c0, W, R, b, peephole=p)
        np.testing.assert_allclose(np.asarray(of), np.asarray(orr),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                                   rtol=2e-4, atol=2e-5)

    def test_vmem_budget_tile_selection(self):
        from deeplearning4j_tpu.ops.pallas.fused_lstm import lstm_tile

        # small model: whole hidden fits in one tile
        assert lstm_tile(8, 128) == 128
        # the r1 failure case: H=1024/B=256 now gets a feasible tile
        assert lstm_tile(256, 1024) is not None
        # absurd size: no tile fits -> requires() rejects, scan fallback
        assert lstm_tile(8192, 8192) is None

    def test_batch_block_plans(self):
        """r4: the planner keeps R grid-invariant at large batches by batch-
        blocking (the bf16-panel sizes the TPU bench runs use)."""
        from deeplearning4j_tpu.ops.pallas.fused_lstm import (lstm_bwd_plan,
                                                              lstm_plan)

        # the r3 demoted shape: fwd chunks the batch, keeps hb == H
        assert lstm_plan(256, 1024) == (64, 1024)
        assert lstm_plan(256, 1024, save_residuals=True) == (32, 1024)
        # bwd tolerates nj == 2 and prefers batch rows (measured, r4)
        assert lstm_bwd_plan(256, 1024) == (64, 512)
        # small-batch selected regimes are unchanged from r3
        assert lstm_plan(32, 1024, save_residuals=True) == (32, 1024)
        assert lstm_plan(64, 256, save_residuals=True) == (64, 256)


class TestBatchBlockedRecurrence:
    """r4: grid (nb, T, nj) — batch-blocked recurrence parity, forced
    chunked plans (nb > 1) so interpret mode exercises the new grid axis
    for both forward and backward, with DIFFERENT fwd/bwd chunk sizes (the
    shipping configuration at B=256/H=1024)."""

    def test_lstm_chunked_parity(self, rng, monkeypatch):
        import deeplearning4j_tpu.ops.pallas.fused_lstm as fl

        B, T, F, H = 64, 12, 16, 128
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        c0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * .1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * .1)
        b = jnp.asarray(rng.normal(size=(4 * H,)).astype(np.float32) * .1)
        p = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * .1)
        monkeypatch.setattr(fl, "lstm_plan", lambda BB, HH, **kw: (16, HH))
        monkeypatch.setattr(fl, "lstm_bwd_plan",
                            lambda BB, HH, **kw: (32, HH))
        of, (hf, cf) = fl.fused_lstm_layer(x, h0, c0, W, R, b, peephole=p)
        orr, (hr, cr) = lstm_layer(x, h0, c0, W, R, b, peephole=p)
        np.testing.assert_allclose(np.asarray(of), np.asarray(orr),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cf), np.asarray(cr),
                                   rtol=2e-4, atol=2e-5)
        gk = jax.grad(lambda a: fl.fused_lstm_layer(
            a[0], h0, c0, a[1], a[2], b, peephole=p)[0].sum())((x, W, R))
        gs = jax.grad(lambda a: lstm_layer(
            a[0], h0, c0, a[1], a[2], b, peephole=p)[0].sum())((x, W, R))
        for name, a, b_ in zip(("x", "W", "R"), gk, gs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} chunked")

    def test_gru_chunked_parity(self, rng, monkeypatch):
        import deeplearning4j_tpu.ops.pallas.fused_gru as fg
        from deeplearning4j_tpu.ops.recurrent import gru_layer

        B, T, F, H = 64, 12, 16, 128
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 3 * H)).astype(np.float32) * .1)
        R = jnp.asarray(rng.normal(size=(H, 3 * H)).astype(np.float32) * .1)
        b = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * .1)
        monkeypatch.setattr(fg, "gru_plan", lambda BB, HH, **kw: (16, HH))
        monkeypatch.setattr(fg, "gru_bwd_plan", lambda BB, HH, **kw: (32, HH))
        og, hg = fg.fused_gru_layer(x, h0, W, R, b)
        osr, hsr = gru_layer(x, h0, W, R, b)
        np.testing.assert_allclose(np.asarray(og), np.asarray(osr),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(hg), np.asarray(hsr),
                                   rtol=2e-4, atol=2e-5)
        gk = jax.grad(lambda a: fg.fused_gru_layer(
            a[0], h0, a[1], a[2], b)[0].sum())((x, W, R))
        gs = jax.grad(lambda a: gru_layer(
            a[0], h0, a[1], a[2], b)[0].sum())((x, W, R))
        for name, a, b_ in zip(("x", "W", "R"), gk, gs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} chunked")


class TestPallasLRN:
    def test_matches_xla_lowering(self, rng):
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops.convolution import lrn as xla_lrn
        from deeplearning4j_tpu.ops.pallas import pallas_lrn

        x = jnp.asarray(rng.normal(size=(2, 8, 8, 64)).astype(np.float32))
        got = np.asarray(pallas_lrn(x))
        want = np.asarray(xla_lrn(x))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    def test_gradient_matches(self, rng):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops.convolution import lrn as xla_lrn
        from deeplearning4j_tpu.ops.pallas import pallas_lrn

        x = jnp.asarray(rng.normal(size=(1, 4, 4, 64)).astype(np.float32))
        g1 = jax.grad(lambda a: (pallas_lrn(a) ** 2).sum())(x)
        g2 = jax.grad(lambda a: (xla_lrn(a) ** 2).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-4, atol=2e-6)

    def test_registry_selection(self, rng, monkeypatch):
        """r4: LRN is default-ON again — the banded backward kernel fixed
        the r3 train-path demotion (measured 1.26x fwd / 1.47x train at the
        AlexNet shape, BASELINE.md). Structural bounds still gate small
        inputs."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.common.env import env
        from deeplearning4j_tpu.ops.registry import get_op

        big = jnp.zeros((4, 32, 32, 64), jnp.float32)   # 4096 pixels
        small = jnp.zeros((1, 4, 4, 8), jnp.float32)
        op = get_op("lrn")
        assert op.select(big).platform == "pallas"       # default-on (r4)
        assert op.select(small).platform == "xla"        # structural holds
        monkeypatch.setattr(env, "force_pallas", True)
        assert op.select(small).platform != "pallas"     # requires() wins
        monkeypatch.setattr(env, "disable_pallas", True)
        assert op.select(big).platform == "xla"          # kill switch

    def test_bwd_is_kernel_not_recompute(self, rng, monkeypatch):
        """r4: the vjp must run the banded backward kernel (_lrn_backward),
        not autodiff through the XLA lowering (the r3 behavior that demoted
        the train path to 0.45x)."""
        import importlib

        import jax
        import jax.numpy as jnp

        mod = importlib.import_module("deeplearning4j_tpu.ops.pallas.lrn")
        called = []
        orig = mod._lrn_backward

        def spy(*a, **k):
            called.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(mod, "_lrn_backward", spy)
        x = jnp.asarray(rng.normal(size=(1, 4, 4, 64)).astype(np.float32))
        jax.grad(lambda a: (mod.pallas_lrn(a) ** 2).sum())(x)
        assert called, "LRN backward kernel was not used in the vjp"

    def test_even_depth_matches_xla(self, rng):
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops.convolution import lrn as xla_lrn
        from deeplearning4j_tpu.ops.pallas import pallas_lrn

        x = jnp.asarray(rng.normal(size=(2, 4, 4, 32)).astype(np.float32))
        for depth in (2, 3, 4, 5):
            got = np.asarray(pallas_lrn(x, depth=depth))
            want = np.asarray(xla_lrn(x, depth=depth))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                       err_msg=f"depth={depth}")


class TestLayerPathSelection:
    def test_transformer_layer_reaches_flash_kernel(self, rng, monkeypatch):
        """The cuDNN-helper pattern end-to-end: a plain TransformerEncoderLayer
        on a long unmasked sequence must route its attention through the
        Pallas flash kernel via the registry (not the pinned XLA lowering)."""

        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderLayer
        from deeplearning4j_tpu.ops.registry import get_op

        op_obj = get_op("dot_product_attention")
        impl = next(im for im in op_obj.impls if im.platform == "pallas")
        calls = []
        orig_fn = impl.fn

        def spy(*a, **k):
            calls.append(1)
            return orig_fn(*a, **k)

        monkeypatch.setattr(impl, "fn", spy)
        T, H, Dh = 2048, 2, 128
        D = H * Dh
        layer = TransformerEncoderLayer(d_model=D, n_heads=H)
        params, state = layer.init(jax.random.key(0), InputType.recurrent(D, T))
        x = jnp.asarray(rng.normal(size=(1, T, D)).astype(np.float32))
        out, _ = layer.apply(params, state, x)
        assert out.shape == (1, T, D)
        assert calls, "flash kernel was not selected from the layer path"

    def test_masked_attention_safe_under_force_pallas(self, rng, monkeypatch):
        """Masked layer attention stays CORRECT when DL4J_TPU_FORCE_PALLAS
        forces the registry's pallas impls. r4: the layer tier's key-padding
        mask now structurally qualifies for the kernel, so this exercises the
        masked kernel end-to-end from the layer path and asserts parity with
        the un-forced (XLA) result."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.common.env import env
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

        T, D = 8, 8
        layer = SelfAttentionLayer(n_out=D, n_heads=2)
        params, state = layer.init(jax.random.key(0), InputType.recurrent(D, T))
        x = jnp.asarray(rng.normal(size=(2, T, D)).astype(np.float32))
        mask = jnp.asarray(np.array([[1] * 5 + [0] * 3, [1] * 8], np.float32))
        ref, _ = layer.apply(params, state, x, mask=mask)
        monkeypatch.setattr(env, "force_pallas", True)
        out, _ = layer.apply(params, state, x, mask=mask)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestFlashAttentionBackward:
    """The flash backward (the fused kernel; the dq and dk/dv pair where a
    head's dq does not fit) vs XLA's autodiff through the plain lowering —
    the cuDNN-parity pattern for gradients. Exercises causal block skipping,
    ragged tail blocks, and the saved-logsumexp recompute."""

    @pytest.mark.parametrize("kmask", [False, True], ids=["no_mask", "kmask"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("causal,seq_q,seq_k", [
        (False, 256, 256), (True, 256, 256),    # whole tiles of (64, 128)
        (False, 200, 200), (True, 200, 200),    # a ragged tail on both axes
        (False, 192, 320),                      # Tq != Tk
    ])
    def test_fused_call_is_the_two_calls_to_the_last_bit(self, rng, causal,
                                                         seq_q, seq_k, dtype,
                                                         kmask):
        """Several tiles on both axes, so dq's rows are added to over
        k-blocks and dk / dv over q-blocks, in the two calls' order."""
        B, H, D = 2, 2, 128
        q, do = (jnp.asarray(rng.normal(size=(B, H, seq_q, D)), dtype)
                 for _ in range(2))
        k, v = (jnp.asarray(rng.normal(size=(B, H, seq_k, D)), dtype)
                for _ in range(2))
        km = None
        if kmask:
            m = np.ones((B, seq_k), np.float32)
            m[0, seq_k // 2:] = 0        # whole k-blocks masked out
            m[1, :] = 0                  # every row of this example fully masked
            km = jnp.asarray(m)
        kw = dict(causal=causal, scale=D ** -0.5, interpret=True, kmask=km)
        out, lse = flash_module._flash_forward(q, k, v, block_q=64,
                                               block_k=128, **kw)
        delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(
            axis=-1, keepdims=True)
        fused, two = (flash_module._flash_backward_at(
            flash_module.BwdTiles(64, 128, fused), q, k, v, do, lse, delta,
            **kw) for fused in (True, False))
        for name, a, b in zip(("dq", "dk", "dv"), fused, two):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype == np.float32 and a.shape == b.shape
            assert np.isfinite(a).all(), name
            np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32),
                                          err_msg=name)
        assert float(jnp.abs(fused[0]).max()) > 0

    @pytest.mark.parametrize("fused", [True, False])
    def test_the_layout_is_bwd_tiles_choice(self, monkeypatch, kernel_calls,
                                            fused):
        """``_flash_backward`` runs what ``bwd_tiles`` says, at its tiles."""
        monkeypatch.setattr(flash_module, "bwd_tiles",
                            lambda *a: flash_module.BwdTiles(64, 128, fused))
        q = jnp.ones((1, 1, 256, 128), jnp.float32)
        calls = kernel_calls(jax.grad(lambda q: flash_attention(q, q, q).sum()), q)
        assert calls == ({"flash_attention_fwd": 1, "flash_attention_bwd": 1}
                         if fused else
                         {"flash_attention_fwd": 1, "flash_attention_bwd_dq": 1,
                          "flash_attention_bwd_dkv": 1})

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(2, 2, 256, 128), (1, 2, 200, 128)])
    def test_grads_match_xla(self, rng, causal, shape):
        import jax

        B, H, T, D = shape
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        do = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))

        _, vjp_f = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=128), q, k, v)
        _, vjp_r = jax.vjp(lambda q, k, v: dot_product_attention(
            q, k, v, causal=causal), q, k, v)
        for name, a, b in zip("qkv", vjp_f(do), vjp_r(do)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"d{name} causal={causal}")

    def test_bwd_is_kernel_not_recompute(self, monkeypatch):
        """The vjp must run the Pallas backward (flash_block_bwd), not fall
        back to autodiff through the XLA lowering."""
        import importlib

        import jax

        fa = importlib.import_module(
            "deeplearning4j_tpu.ops.pallas.flash_attention")
        called = []
        orig = fa._flash_backward

        def spy(*a, **kw):
            called.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(fa, "_flash_backward", spy)
        q = jnp.ones((1, 1, 256, 128), jnp.float32)
        jax.grad(lambda q: fa.flash_attention(q, q, q).sum())(q)
        assert called, "flash backward kernel was not used in the vjp"

    def test_bf16_inputs(self, rng):
        import jax

        B, H, T, D = 1, 2, 256, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D))).astype(jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, H, T, D))).astype(jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, H, T, D))).astype(jnp.bfloat16)
        g = jax.grad(lambda q: flash_attention(q, k, v, causal=True)
                     .astype(jnp.float32).sum())(q)
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, np.float32)).all()


def _band_reference(q, k, v, window, causal=True):
    """Dense attention under an explicit band mask; k / v heads repeated over
    their groups of query heads."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(k.shape[2])[None, :]
    keep = j <= i if causal else jnp.ones((q.shape[2], k.shape[2]), bool)
    if window is not None:
        keep &= i - j < window
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)


class TestFlashWindowAndGroupedHeads:
    """A causal window and key-value heads shared by a group of query heads,
    in interpret mode against the dense band mask, forward and backward."""

    @staticmethod
    def qkv(rng, heads, kv_heads, T, D=64, B=2):
        return (jnp.asarray(rng.standard_normal((B, h, T, D)), jnp.float32)
                for h in (heads, kv_heads, kv_heads))

    @pytest.mark.parametrize("T,heads,kv_heads,window,bq,bk", [
        (256, 4, 2, 64, 64, 128),       # the window divides the tiles
        (256, 4, 1, 100, 64, 64),       # it does not; one key-value head for all
        (384, 2, 2, 37, 128, 64),       # narrower than a tile, equal heads
        (320, 8, 2, 130, 64, 128),      # a ragged last k-block
        (256, 2, 1, 1000, 64, 128),     # wider than the sequence: plain causal
        (256, 4, 2, None, 64, 128),     # grouped heads alone
    ])
    def test_forward_and_backward_match_the_dense_band(self, rng, T, heads, kv_heads,
                                                       window, bq, bk):
        q, k, v = self.qkv(rng, heads, kv_heads, T)
        got, got_grads = jax.value_and_grad(lambda *a: (flash_attention(
            *a, causal=True, window=window, block_q=bq, block_k=bk) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        want, want_grads = jax.value_and_grad(
            lambda *a: (_band_reference(*a, window) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        for g, w in zip(got_grads, want_grads):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=5e-5)

    @pytest.mark.parametrize("window", [None, 48])
    def test_xla_lowering_has_the_same_semantics(self, rng, window):
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self.qkv(rng, 4, 2, 96, D=16)
        got, got_grads = jax.value_and_grad(lambda *a: (dot_product_attention(
            *a, causal=True, window=window) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        want, want_grads = jax.value_and_grad(
            lambda *a: (_band_reference(*a, window) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        for g, w in zip(got_grads, want_grads):
            np.testing.assert_allclose(g, w, atol=2e-5)

    @pytest.mark.parametrize("window", [None, 100])
    def test_fused_backward_equals_the_two_calls_bit_for_bit(self, rng, window):
        q, k, v = self.qkv(rng, 4, 2, 256)
        out, lse = flash_module._flash_forward(
            q, k, v, causal=True, scale=0.125, block_q=64, block_k=128, interpret=True,
            window=window)
        do = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
        delta = (do * out).sum(-1, keepdims=True)
        fused, two = (flash_module._flash_backward_at(
            flash_module.BwdTiles(64, 128, layout), q, k, v, do, lse, delta, causal=True,
            scale=0.125, interpret=True, window=window) for layout in (True, False))
        for a, b in zip(fused, two):
            assert bool((a == b).all())

    @pytest.mark.parametrize("causal,digest", [
        (True, "15b2ccad5d0e862bc072d133dabb3732b2203770dddaf5aa90b82e9b73f2020c"),
        (False, "ed228247cb400a1eac3f24cdf329312ef103350d4ed94b7a0147ba20067062db"),
    ])
    def test_no_window_and_equal_heads_give_the_parents_bits(self, causal, digest):
        """``window=None`` with equal head counts is the kernel it was: output
        and the three gradients of a seeded call, by the digest the parent
        commit (e480ce2) gives for the same lines."""
        import hashlib

        rng = np.random.default_rng(7)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
                   for _ in range(3))
        out, grads = jax.value_and_grad(lambda *a: (flash_attention(
            *a, causal=causal, block_q=64, block_k=128) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        seen = hashlib.sha256()
        for a in (out, *grads):
            seen.update(np.asarray(a).tobytes())
        assert seen.hexdigest() == digest

    def test_band_steps_count_the_blocks_a_band_reaches(self):
        """At the expert cell's shape (512 x 1024 tiles, window 1,024) a q-block
        reaches 2 of the 8 k-blocks and a k-block 4 of the 16 q-blocks."""
        assert flash_module._band_steps(16, 512, 1024, 8, 1023, 0) == 2
        assert flash_module._band_steps(8, 1024, 512, 16, 0, 1023) == 4
        assert flash_module._band_blocks(5, 512, 1024, 8, 1023, 0) == (1, 2)
        assert flash_module._band_blocks(7, 1024, 512, 16, 0, 1023) == (14, 15)

    def test_registry_predicates_take_a_window_and_groups(self, rng):
        q, k, v = self.qkv(rng, 4, 2, 2048, D=64, B=1)
        assert flash_module._flash_applicable(q, k, v, causal=True, window=1024)
        assert flash_module._flash_requires(q, k, v, causal=True, window=1024)
        assert not flash_module._flash_requires(q, k, v, causal=False, window=1024)
        assert not flash_module._flash_requires(
            jnp.concatenate([q, q[:, :1]], axis=1), k, v, causal=True)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, window=8)


class TestFlashValuesOfTheirOwnWidth:
    """v narrower than q and k (latent attention: keys of 192, values of 128),
    in interpret mode against XLA's lowering, the fused backward and the two
    calls."""

    @staticmethod
    def qkv(rng, T, D, Dv, heads=2, kv_heads=2, B=1):
        return tuple(jnp.asarray(rng.standard_normal((B, h, T, d)), jnp.float32)
                     for h, d in ((heads, D), (kv_heads, D), (kv_heads, Dv)))

    @pytest.mark.parametrize("T,D,Dv,heads,kv_heads,causal,window", [
        (256, 48, 32, 2, 2, True, None),        # the latent layer's 3 : 2
        (256, 48, 32, 2, 2, False, None),
        (320, 24, 16, 4, 2, True, None),        # a ragged last block, grouped heads
        (256, 48, 32, 2, 1, True, 100),         # under a window
        (256, 32, 48, 2, 2, True, None),        # wider values
    ])
    def test_forward_and_backward_match_xlas_lowering(self, rng, T, D, Dv, heads, kv_heads,
                                                      causal, window):
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self.qkv(rng, T, D, Dv, heads, kv_heads)
        do = jnp.asarray(rng.standard_normal((1, heads, T, Dv)), jnp.float32)

        def scored(attend, **tiles):
            return jax.value_and_grad(lambda *a: (attend(
                *a, causal=causal, window=window, **tiles) * do).sum(), argnums=(0, 1, 2))

        got, got_grads = scored(flash_attention, block_q=64, block_k=128)(q, k, v)
        want, want_grads = scored(dot_product_attention)(q, k, v)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for g, w in zip(got_grads, want_grads):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=5e-5)

    @pytest.mark.parametrize("fused", [True, False], ids=["fused", "two_calls"])
    def test_each_backward_layout_against_xlas_lowering(self, rng, fused):
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self.qkv(rng, 256, 48, 32)
        do = jnp.asarray(rng.standard_normal((1, 2, 256, 32)), jnp.float32)
        scale = 48 ** -0.5
        out, lse = flash_module._flash_forward(q, k, v, causal=True, scale=scale, block_q=64,
                                               block_k=128, interpret=True)
        assert out.shape == (1, 2, 256, 32)
        delta = (do * out).sum(-1, keepdims=True)
        got = flash_module._flash_backward_at(
            flash_module.BwdTiles(64, 128, fused), q, k, v, do, lse, delta, causal=True,
            scale=scale, interpret=True)
        want = jax.grad(lambda *a: (dot_product_attention(*a, causal=True) * do).sum(),
                        argnums=(0, 1, 2))(q, k, v)
        assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=5e-5)

    def test_fused_and_two_calls_agree_bit_for_bit(self, rng):
        q, k, v = self.qkv(rng, 256, 48, 32)
        out, lse = flash_module._flash_forward(q, k, v, causal=True, scale=0.1, block_q=64,
                                               block_k=128, interpret=True)
        do = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
        delta = (do * out).sum(-1, keepdims=True)
        fused, two = (flash_module._flash_backward_at(
            flash_module.BwdTiles(64, 128, layout), q, k, v, do, lse, delta, causal=True,
            scale=0.1, interpret=True) for layout in (True, False))
        for a, b in zip(fused, two):
            assert bool((a == b).all())

    def test_the_registry_takes_the_kernel_at_the_latent_layers_shape(self):
        q, k, v = (jax.ShapeDtypeStruct((1, 32, 8192, d), jnp.bfloat16) for d in (192, 192, 128))
        assert flash_module._flash_requires(q, k, v, causal=True)
        assert flash_module._flash_applicable(q, k, v, causal=True)
        narrow_k = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
        assert not flash_module._flash_requires(q, narrow_k, v, causal=True)     # q and k differ

    def test_the_estimate_counts_each_width_at_its_own_lanes(self):
        """k, q, dq and dk at 256 lanes (192 padded), v, do and dv at 128: at T =
        8,192 a head's dq is 8 MB, the fused call fits at (512, 512) alone, and a
        zero-padded v would not have changed that but would run half again the
        p v, dv and dp products."""
        MB = 2 ** 20
        assert flash_module.bwd_vmem_bytes(512, 1024, 192, 2, 8192, 128) / MB == 18.5
        assert flash_module.bwd_vmem_bytes(512, 512, 192, 2, 8192, 128) / MB == 14.125
        assert flash_module.bwd_vmem_bytes(512, 512, 192, 2, 8192) / MB == 15.0
        assert flash_module.bwd_tiles(512, 1024, 192, 8192, 8192, 2, 128) == (512, 512, True)
        assert flash_module.bwd_tiles(512, 1024, 192, 16384, 16384, 2, 128) == (512, 1024, False)
        # one width: the parent's figures
        assert flash_module.bwd_vmem_bytes(512, 1024, 128, 2, 4096, 128) == \
            flash_module.bwd_vmem_bytes(512, 1024, 128, 2, 4096) == 11 * MB


class TestFlashAttentionUnderCheckpoint:
    """``_flash_fwd`` names its output and log-sum-exp: a checkpoint whose
    policy keeps the two names holds the T-sized results and the backward
    pass does not run the T^2 forward kernel again."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_a_names_policy_keeps_the_forwards_results(self, rng, causal,
                                                       kernel_calls):
        q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 256, 128))
                               .astype(np.float32)) for _ in range(3))

        def attend(q, k, v):
            return flash_attention(q * 2.0, k, v, causal=causal) * 3.0

        def grad_of(f):
            return jax.grad(lambda q, k, v: f(q, k, v).sum(), argnums=(0, 1, 2))

        policy = jax.checkpoint_policies.save_only_these_names(
            flash_module.SAVED_OUT, flash_module.SAVED_LSE)
        plain = grad_of(attend)
        kept = grad_of(jax.checkpoint(attend, policy=policy))
        bare = grad_of(jax.checkpoint(attend))
        once = {"flash_attention_fwd": 1, "flash_attention_bwd": 1}
        assert kernel_calls(plain, q, k, v) == once
        assert kernel_calls(kept, q, k, v) == once
        assert kernel_calls(bare, q, k, v) == {**once, "flash_attention_fwd": 2}
        for a, b in zip(kept(q, k, v), plain(q, k, v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_one_name_alone_does_not_spare_the_forward(self, rng, kernel_calls):
        """Both results come from the one call: keep ``out`` without ``lse``
        (or the reverse) and the backward pass still has to run it."""
        q = jnp.asarray(rng.normal(size=(1, 1, 128, 128)).astype(np.float32))
        for name in (flash_module.SAVED_OUT, flash_module.SAVED_LSE):
            policy = jax.checkpoint_policies.save_only_these_names(name)
            f = jax.grad(lambda q: jax.checkpoint(  # noqa: B023
                lambda q: flash_attention(q * 2.0, q, q),
                policy=policy)(q).sum())  # noqa: B023
            assert kernel_calls(f, q)["flash_attention_fwd"] == 2


class TestFlashBackwardCompilesForTheV5e:
    """The backward at the tiles ``bwd_tiles`` gives, compiled for a described
    chip; nothing runs. These shapes are too large for XLA to keep an operand
    in VMEM, so the compile sees the kernel's whole need: with two buffers
    for every block the dk/dv kernel at (1024, 1024) needed 16.18-16.68 MB of
    scoped VMEM, over the chip's 16, and compiled or not by what XLA placed
    around it; the fused kernel holds a head's whole dq besides."""

    @pytest.fixture(scope="class")
    def one_chip(self):
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache
        from jax.sharding import SingleDeviceSharding

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()

    @staticmethod
    def compiled(one_chip, shape, tiles):
        B, H, T, D = shape
        wide = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        row = jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32, sharding=one_chip)
        return jax.jit(functools.partial(
            flash_module._flash_backward_at, tiles, causal=True,
            scale=D ** -0.5, interpret=False)).lower(
                wide, wide, wide, wide, row, row).compile().as_text()

    @pytest.mark.parametrize("shape,tiles,calls", [
        ((2, 16, 4096, 128), (512, 1024, True), 1),     # the looped decoder's cell
        ((2, 16, 8192, 128), (512, 1024, True), 1),
        ((2, 16, 16384, 128), (512, 512, True), 1),     # dq alone is 8 MB
        ((8, 12, 4096, 64), (512, 1024, True), 1),      # 64 lanes padded to 128
        ((1, 4, 32768, 128), (1024, 1024, False), 2),   # dq would be 16 MB
    ])
    def test_backward_kernels_fit_scoped_vmem(self, one_chip, shape, tiles,
                                              calls):
        T, D = shape[2:]
        chosen = flash_module.bwd_tiles(512, 1024, D, T, T, 2)
        assert chosen == tiles
        text = self.compiled(one_chip, shape, chosen)
        assert text.count('custom_call_target="tpu_custom_call"') == calls

    @pytest.mark.parametrize("window", [None, 1024])
    def test_grouped_heads_and_a_window_compile_at_the_expert_cells_shape(
            self, one_chip, window):
        """(1, 32 query heads over 4 key-value heads, 8,192, 128): forward and
        the fused backward, with the band and without; dk and dv leave the call
        a query head each and are summed over the group by XLA."""
        B, H, Hkv, T, D = 1, 32, 4, 8192, 128
        tiles = flash_module.bwd_tiles(512, 1024, D, T, T, 2)
        assert tiles == (512, 1024, True)

        def sds(heads, last, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct((B, heads, T, last), dtype, sharding=one_chip)

        q, kv, row = sds(H, D), sds(Hkv, D), sds(H, 1, jnp.float32)
        backward = jax.jit(functools.partial(
            flash_module._flash_backward_at, tiles, causal=True, scale=D ** -0.5,
            interpret=False, window=window)).lower(q, kv, kv, q, row, row).compile()
        assert backward.as_text().count('custom_call_target="tpu_custom_call"') == 1
        assert [o.shape for o in jax.tree.leaves(backward.out_info)] == [
            (B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, D)]
        forward = jax.jit(functools.partial(
            flash_module._flash_forward, causal=True, scale=D ** -0.5, block_q=512,
            block_k=1024, interpret=False, window=window)).lower(q, kv, kv).compile()
        assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1

    @pytest.mark.parametrize("fused,calls", [(True, 1), (False, 2)], ids=["fused", "two_calls"])
    def test_the_block_diffusion_mask_compiles_at_the_diffusion_cells_shape(self, one_chip, fused,
                                                                            calls):
        """(1, 32 query heads over 4 key-value heads, 2 x 8,192, 128), blocks of 4:
        the forward at 512 x 512 tiles and the backward in both layouts, each a
        grid of the visible tiles alone with the tables in scalar memory; the
        fused layout is the one ``bwd_tiles`` gives the shape."""
        B, H, Hkv, T, D = 1, 32, 4, 16384, 128
        tiles = flash_module.bwd_tiles(512, 512, D, T, T, 2)
        assert tiles == (512, 512, True)
        if not fused:
            tiles = flash_module.BwdTiles(1024, 1024, False)

        def sds(heads, last, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct((B, heads, T, last), dtype, sharding=one_chip)

        q, kv, row = sds(H, D), sds(Hkv, D), sds(H, 1, jnp.float32)
        backward = jax.jit(functools.partial(
            flash_module._flash_backward_at, tiles, causal=False, scale=D ** -0.5,
            interpret=False, diffusion=4)).lower(q, kv, kv, q, row, row).compile()
        assert backward.as_text().count('custom_call_target="tpu_custom_call"') == calls
        assert [o.shape for o in jax.tree.leaves(backward.out_info)] == [
            (B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, D)]
        forward = jax.jit(functools.partial(
            flash_module._flash_forward, causal=False, scale=D ** -0.5, block_q=512,
            block_k=flash_module.DIFFUSION_TILE, interpret=False, diffusion=4)).lower(
                q, kv, kv).compile()
        assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1

    @pytest.mark.parametrize("fused,calls", [(True, 1), (False, 2)], ids=["fused", "two_calls"])
    def test_keys_of_192_and_values_of_128_compile_at_the_latent_layers_shape(
            self, one_chip, fused, calls):
        """(1, 32, 8,192, 192 / 128): the forward and the backward in both
        layouts, the fused one at the (512, 512) ``bwd_tiles`` gives it, the two
        calls at the (512, 1024) they get from T = 16,384 on, where a head's dq
        no longer fits."""
        B, H, T, D, Dv = 1, 32, 8192, 192, 128
        tiles = flash_module.bwd_tiles(512, 1024, D, T, T, 2, Dv)
        assert tiles == (512, 512, True)
        if not fused:
            tiles = flash_module.bwd_tiles(512, 1024, D, 2 * T, 2 * T, 2, Dv)
            assert tiles == (512, 1024, False)

        def sds(last, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct((B, H, T, last), dtype, sharding=one_chip)

        qk, v, row = sds(D), sds(Dv), sds(1, jnp.float32)
        backward = jax.jit(functools.partial(
            flash_module._flash_backward_at, tiles, causal=True, scale=D ** -0.5,
            interpret=False)).lower(qk, qk, v, v, row, row).compile()
        assert backward.as_text().count('custom_call_target="tpu_custom_call"') == calls
        assert [o.shape for o in jax.tree.leaves(backward.out_info)] == [
            (B, H, T, D), (B, H, T, D), (B, H, T, Dv)]
        forward = jax.jit(functools.partial(
            flash_module._flash_forward, causal=True, scale=D ** -0.5, block_q=512,
            block_k=1024, interpret=False)).lower(qk, qk, v).compile()
        assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1
        assert [o.shape for o in jax.tree.leaves(forward.out_info)] == [
            (B, H, T, Dv), (B, H, T, 1)]

    def test_delta_rule_kernels_compile_at_the_linear_mixers_shape(self, one_chip, monkeypatch):
        """``[1, 32, 8192, 128]`` bfloat16 (the linear-attention cell's): the
        forward and the backward one Mosaic call each, and between them the
        chunks' starting states (134 MB) the bulk of the temporaries, where the
        plain lowering's stages took 1.48 GB."""
        from deeplearning4j_tpu.ops.pallas import delta_rule as kernels

        monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
        B, H, T, K = 1, 32, 8192, 128

        def sds(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        wide, g, beta = sds((B, H, T, K)), sds((B, H, T, K), jnp.float32), sds((B, H, T), jnp.float32)

        def loss(q, k, v, g, beta):
            o, S = kernels.gated_delta_rule(q, k, v, g, beta)
            return o.astype(jnp.float32).sum() + S.sum()

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            wide, wide, wide, g, beta).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "delta_rule_fwd" in text and "delta_rule_bwd" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 200e6

    @pytest.mark.parametrize("differentiated", [False, True], ids=["value", "gradient"])
    def test_the_indexer_loss_kernels_compile_at_the_sparse_cells_shape(self, one_chip, monkeypatch,
                                                                        differentiated):
        """The keye cell's shape (32 query heads over 4 of 128, 16 index heads of
        64, 16,384 tokens): the log-sum-exp pass and the pass that forms the
        mean weights, and no third call for the gradient, which the second
        makes; the float32 gradients leave it, ``k_i``'s as parts a block of
        queries (268 MB with its 64 lanes padded, the bulk of the temporaries)."""
        from deeplearning4j_tpu.ops.pallas import sparse_attention as kernels

        monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
        B, H, KV, T, D, HI, DI = 1, 32, 4, 16384, 128, 16, 64

        def sds(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        args = (sds((B, H, T, D)), sds((B, KV, T, D)), sds((B, H, T), jnp.float32),
                sds((B, T, T), jnp.int8), sds((B, HI, T, DI)), sds((B, T, DI)),
                sds((B, T, HI), jnp.float32))
        fn = kernels.indexer_loss
        if differentiated:
            fn = jax.value_and_grad(fn, argnums=(4, 5, 6))
        compiled = jax.jit(fn).lower(*args).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "indexer_loss_lse" in text and "indexer_loss_kl" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 450e6

    @pytest.mark.parametrize("kernel,calls", [
        ("pack_rows", 1), ("gather_rows", 2), ("gather_rows_dot", 2), ("gather_sum_rows", 2)])
    def test_row_gather_kernels_compile_at_the_expert_cells_shape(self, one_chip, kernel, calls):
        """8,192 tokens of 2,304 bfloat16, top-8: 65,536 sorted rows. Each gather
        is its kernel and the packing of its table; the indices (256 KB, with
        the weights 512) lie in SMEM beside a tile's slabs in VMEM."""
        from deeplearning4j_tpu.ops.pallas import row_gather

        T, K, D = 8192, 8, 2304

        def sds(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        table, rows, n = sds((T, D)), sds((T * K, D)), sds((), jnp.int32)
        index, place = sds((T * K,), jnp.int32), sds((T, K), jnp.int32)
        scale, weights = sds((T * K,), jnp.float32), sds((T, K), jnp.float32)
        args = {"pack_rows": (rows, n), "gather_rows": (table, index, n),
                "gather_rows_dot": (table, index, n, scale, rows),
                "gather_sum_rows": (rows, place, n, weights)}[kernel]
        text = jax.jit(functools.partial(getattr(row_gather, kernel), interpret=False)).lower(
            *args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == calls

    def test_the_expert_layers_step_moves_no_pair_rows_but_by_its_kernels(self, one_chip,
                                                                         monkeypatch):
        """The layer's forward and gradient at the cell's shape, compiled for the
        chip: nine kernel calls move rows (four packings, the gather, the gather
        with the weights' products, two sums), XLA gathers scalars only, and
        neither combine's forward nor dispatch's backward has an array of
        ``tokens x top_k`` rows of features."""
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers import SparseExpertsLayer
        from deeplearning4j_tpu.ops.pallas import grouped_matmul, row_gather

        for module in (row_gather, grouped_matmul):     # the CPU is the default backend here
            monkeypatch.setattr(module, "interpret_mode", lambda: False)
        T, D = 8192, 2304
        layer = SparseExpertsLayer(n_experts=64, top_k=8, d_expert=896, experts_held=(0, 16))
        params, state = jax.eval_shape(
            lambda: layer.init(jax.random.key(0), InputType.recurrent(D, T)))

        def on_chip(leaf, dtype=None):
            return jax.ShapeDtypeStruct(leaf.shape, dtype or leaf.dtype, sharding=one_chip)

        params = {k: on_chip(v, jnp.float32 if k == "Wr" else jnp.bfloat16)
                  for k, v in params.items()}
        x = jax.ShapeDtypeStruct((1, T, D), jnp.bfloat16, sharding=one_chip)
        text = jax.jit(jax.grad(
            lambda p, x: (layer.apply(p, jax.tree.map(jnp.zeros_like, state), x)[0]
                          .astype(jnp.float32) ** 2).sum(), argnums=(0, 1))).lower(
                params, x).compile().as_text()
        lines = text.splitlines()
        calls = [name for line in lines if 'custom_call_target="tpu_custom_call"' in line
                 for name in re.findall(r'/(\w+)/pallas_call"', line)]     # not the products' jit(gmm)
        assert sorted(calls) == sorted(["pack_rows"] * 4 + ["gather_rows", "gather_rows_dot"]
                                       + ["gather_sum_rows"] * 2)
        pair_rows = re.compile(r"= \(?(bf16|f32)\[(65536,2304|8192,8,2304)\]")
        for line in lines:
            name = re.search(r'op_name="([^"]*)"', line)
            if name is None:
                continue
            if re.search(r" gather\(", line):
                assert "2304" not in line.split("metadata")[0], line      # scalars only
            forward = "transpose(" not in name.group(1)
            if (re.search(r"[(/]combine[)/]", name.group(1)) and forward) or (
                    re.search(r"[(/]dispatch[)/]", name.group(1)) and not forward):
                assert not pair_rows.search(line), line

    def test_the_estimate_stands_just_over_the_compilers_figure(self, one_chip):
        """At (1024, 1024) the fused call does not fit beside the cell's dq:
        the compiler names its figure, and ``bwd_vmem_bytes`` is to count
        what is there, a little high."""
        shape = (2, 16, 4096, 128)
        with pytest.raises(Exception, match="Scoped allocation") as refused:
            self.compiled(one_chip, shape, flash_module.BwdTiles(1024, 1024, True))
        asked, limit = re.search(r"size ([0-9.]+)M and limit ([0-9.]+)M",
                                 str(refused.value)).groups()
        assert float(limit) == 16.0
        estimate = flash_module.bwd_vmem_bytes(1024, 1024, 128, 2,
                                               dq_rows=4096) / 2 ** 20
        assert estimate == 17.5
        assert float(asked) <= estimate <= 1.05 * float(asked)
        assert estimate * 2 ** 20 > flash_module.VMEM_BUDGET_BYTES


class TestFusedLSTMGradients:
    def test_grads_match_scan(self, rng):
        """custom_vjp: kernel forward, scan-recompute backward — gradients
        must equal differentiating the scan path directly."""
        B, T, F, H = 4, 6, 8, 128
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.zeros((B, H))
        c0 = jnp.zeros((B, H))
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * 0.1)
        b = jnp.zeros((4 * H,))
        p = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * 0.1)
        for peep in (None, p):
            gk = jax.grad(lambda W: fused_lstm_layer(
                x, h0, c0, W, R, b, peephole=peep)[0].sum())(W)
            gs = jax.grad(lambda W: lstm_layer(
                x, h0, c0, W, R, b, peephole=peep)[0].sum())(W)
            np.testing.assert_allclose(np.asarray(gk), np.asarray(gs),
                                       rtol=2e-4, atol=2e-5)


class TestFusedLSTMBackwardKernel:
    """The dedicated reverse-time Pallas backward kernel (the
    cudnnRNNBackwardData-parity pass) vs autodiff through the scan lowering.
    """

    def _mk(self, rng, B, T, F, H, scale=0.1):
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * scale)
        c0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * scale)
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * scale)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * scale)
        b = jnp.asarray(rng.normal(size=(4 * H,)).astype(np.float32) * scale)
        p = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * scale)
        return x, h0, c0, W, R, b, p

    def test_bwd_is_kernel_not_recompute(self, monkeypatch):
        """The vjp must run the Pallas backward kernel, not fall back to
        autodiff through the scan."""
        import deeplearning4j_tpu.ops.pallas.fused_lstm as fl

        called = []
        orig = fl._bwd_recurrence

        def spy(*a, **kw):
            called.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(fl, "_bwd_recurrence", spy)
        x = jnp.ones((8, 3, 8), jnp.float32)
        h0 = jnp.zeros((8, 128))
        W = jnp.ones((8, 512), jnp.float32) * 0.01
        R = jnp.ones((128, 512), jnp.float32) * 0.01
        b = jnp.zeros((512,))
        jax.grad(lambda W: fl.fused_lstm_layer(
            x, h0, h0, W, R, b)[0].sum())(W)
        assert called, "LSTM backward kernel was not used in the vjp"

    @pytest.mark.parametrize("reverse", [False, True])
    @pytest.mark.parametrize("peephole", [False, True])
    def test_all_argnum_grads_match_scan(self, rng, reverse, peephole):
        """Gradients wrt every differentiable input, with cotangents flowing
        through the sequence output AND the (hT, cT) final-state outputs."""
        B, T, F, H = 8, 5, 8, 128
        x, h0, c0, W, R, b, p = self._mk(rng, B, T, F, H)
        peep = p if peephole else None
        wseq = jnp.asarray(rng.normal(size=(B, T, H)).astype(np.float32))

        def loss(fn, *args):
            out, (hT, cT) = fn(*args, peephole=peep, forget_gate_bias=1.0,
                               reverse=reverse)
            return (out * wseq).sum() + 0.5 * hT.sum() + 0.25 * (cT ** 2).sum()

        args = (x, h0, c0, W, R, b)
        argnums = tuple(range(6))
        gk = jax.grad(lambda *a: loss(fused_lstm_layer, *a), argnums)(*args)
        gs = jax.grad(lambda *a: loss(lstm_layer, *a), argnums)(*args)
        for name, a, b_ in zip(("x", "h0", "c0", "W", "R", "b"), gk, gs):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} reverse={reverse} peephole={peephole}")
        if peephole:
            gpk = jax.grad(lambda pp: loss(
                lambda *a, **k: fused_lstm_layer(*a, **{**k, "peephole": pp}),
                *args))(p)
            gps = jax.grad(lambda pp: loss(
                lambda *a, **k: lstm_layer(*a, **{**k, "peephole": pp}),
                *args))(p)
            np.testing.assert_allclose(np.asarray(gpk), np.asarray(gps),
                                       rtol=2e-4, atol=2e-5, err_msg="dp")

    def test_big_shape_hidden_tiled_parity(self, rng):
        """H=1024/B=256 — the shape the VERDICT names: the bwd tile selector
        must pick a real hidden tile (128) and the tiled kernel's gradients
        must match the scan."""
        from deeplearning4j_tpu.ops.pallas.fused_lstm import lstm_bwd_tile

        assert lstm_bwd_tile(256, 1024) == 128
        B, T, F, H = 256, 3, 16, 1024
        x, h0, c0, W, R, b, p = self._mk(rng, B, T, F, H, scale=0.02)
        gk = jax.grad(lambda R: fused_lstm_layer(
            x, h0, c0, W, R, b, peephole=p)[0].sum())(R)
        gs = jax.grad(lambda R: lstm_layer(
            x, h0, c0, W, R, b, peephole=p)[0].sum())(R)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gs),
                                   rtol=2e-4, atol=2e-5)

    def test_bwd_tile_budget(self):
        from deeplearning4j_tpu.ops.pallas.fused_lstm import lstm_bwd_tile

        assert lstm_bwd_tile(8, 128) == 128
        # pathological: never fits
        assert lstm_bwd_tile(8192, 8192) is None

    def test_scan_fallback_flag(self, rng, monkeypatch):
        """DL4J_TPU_LSTM_SCAN_BWD forces the recompute path (A/B switch);
        gradients must be identical either way."""
        import deeplearning4j_tpu.ops.pallas.fused_lstm as fl
        from deeplearning4j_tpu.common.env import env

        called = []
        orig = fl._bwd_recurrence
        monkeypatch.setattr(fl, "_bwd_recurrence",
                            lambda *a, **k: (called.append(1), orig(*a, **k))[1])
        B, T, F, H = 8, 4, 8, 128
        x, h0, c0, W, R, b, p = self._mk(rng, B, T, F, H)
        g_kernel = jax.grad(lambda W: fl.fused_lstm_layer(
            x, h0, c0, W, R, b, peephole=p)[0].sum())(W)
        assert called
        called.clear()
        monkeypatch.setattr(env, "lstm_scan_bwd", True)
        g_scan = jax.grad(lambda W: fl.fused_lstm_layer(
            x, h0, c0, W, R, b, peephole=p)[0].sum())(W)
        assert not called, "flag did not force the scan backward"
        np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_scan),
                                   rtol=2e-4, atol=2e-5)

    def test_bf16_finite(self, rng):
        B, T, F, H = 8, 4, 8, 128
        x, h0, c0, W, R, b, p = self._mk(rng, B, T, F, H)
        cast = lambda t: t.astype(jnp.bfloat16)
        g = jax.grad(lambda W: fused_lstm_layer(
            cast(x), cast(h0), cast(c0), W, cast(R), cast(b),
            peephole=cast(p))[0].astype(jnp.float32).sum())(cast(W))
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, np.float32)).all()


class TestFusedLSTMUnalignedHidden:
    """r3: unaligned hidden sizes (the reference's stock 200-unit configs)
    run on the kernel via exact zero-padding — padded lanes carry c = h = 0
    through the whole recurrence, so outputs and ALL gradients match the
    scan bit-for-math."""

    @pytest.mark.parametrize("H", [200, 100])
    @pytest.mark.parametrize("peephole", [False, True])
    def test_forward_and_grads_match_scan(self, rng, H, peephole):
        B, T, F = 8, 6, 10
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.1)
        c0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.1)
        W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * 0.1)
        b = jnp.asarray(rng.normal(size=(4 * H,)).astype(np.float32) * 0.1)
        p = (jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * 0.1)
             if peephole else None)
        of, (hf, cf) = fused_lstm_layer(x, h0, c0, W, R, b, peephole=p,
                                        forget_gate_bias=1.0)
        orr, (hr, cr) = lstm_layer(x, h0, c0, W, R, b, peephole=p,
                                   forget_gate_bias=1.0)
        np.testing.assert_allclose(np.asarray(of), np.asarray(orr),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cf), np.asarray(cr),
                                   rtol=2e-4, atol=2e-5)
        args = (x, h0, c0, W, R, b)
        gk = jax.grad(lambda *a: fused_lstm_layer(
            *a, peephole=p, forget_gate_bias=1.0)[0].sum(),
            argnums=tuple(range(6)))(*args)
        gs = jax.grad(lambda *a: lstm_layer(
            *a, peephole=p, forget_gate_bias=1.0)[0].sum(),
            argnums=tuple(range(6)))(*args)
        for name, a, b_ in zip(("x", "h0", "c0", "W", "R", "b"), gk, gs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"d{name} H={H}")

    def test_registry_selects_kernel_for_unaligned_h(self):
        op = get_op("lstm_layer")
        x = jnp.zeros((8, 4, 16))
        h0 = c0 = jnp.zeros((8, 200))
        assert op.select(x, h0, c0, jnp.zeros((16, 800)),
                         jnp.zeros((200, 800)),
                         jnp.zeros((800,))).platform == "pallas"


class TestFusedGRU:
    """Fused GRU kernel (CUDNN_GRU-mode analog) vs the scan lowering —
    forward parity, full-argnum gradient parity (backward kernel), tiling,
    padding, selection."""

    def _mk(self, rng, B, T, F, H, scale=0.1):
        from deeplearning4j_tpu.ops.recurrent import gru_layer  # noqa: F401
        x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
        h0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * scale)
        W = jnp.asarray(rng.normal(size=(F, 3 * H)).astype(np.float32) * scale)
        R = jnp.asarray(rng.normal(size=(H, 3 * H)).astype(np.float32) * scale)
        b = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * scale)
        return x, h0, W, R, b

    @pytest.mark.parametrize("reverse", [False, True])
    def test_matches_scan(self, rng, reverse):
        from deeplearning4j_tpu.ops.pallas import fused_gru_layer
        from deeplearning4j_tpu.ops.recurrent import gru_layer
        x, h0, W, R, b = self._mk(rng, 4, 6, 8, 128)
        ok, hk = fused_gru_layer(x, h0, W, R, b, reverse=reverse)
        os_, hs = gru_layer(x, h0, W, R, b, reverse=reverse)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(os_),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(hk), np.asarray(hs),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("reverse", [False, True])
    def test_all_argnum_grads_match_scan(self, rng, reverse):
        from deeplearning4j_tpu.ops.pallas import fused_gru_layer
        from deeplearning4j_tpu.ops.recurrent import gru_layer
        B, T, F, H = 8, 5, 8, 128
        x, h0, W, R, b = self._mk(rng, B, T, F, H)
        wseq = jnp.asarray(rng.normal(size=(B, T, H)).astype(np.float32))

        def loss(fn, *args):
            out, hT = fn(*args, reverse=reverse)
            return (out * wseq).sum() + 0.5 * (hT ** 2).sum()

        argnums = tuple(range(5))
        gk = jax.grad(lambda *a: loss(fused_gru_layer, *a), argnums)(
            x, h0, W, R, b)
        gs = jax.grad(lambda *a: loss(gru_layer, *a), argnums)(
            x, h0, W, R, b)
        for name, a, b_ in zip(("x", "h0", "W", "R", "b"), gk, gs):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} reverse={reverse}")

    def test_bwd_is_kernel_not_recompute(self, monkeypatch):
        import deeplearning4j_tpu.ops.pallas.fused_gru as fg

        called = []
        orig = fg._bwd_recurrence

        def spy(*a, **kw):
            called.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(fg, "_bwd_recurrence", spy)
        x = jnp.ones((8, 3, 8), jnp.float32)
        h0 = jnp.zeros((8, 128))
        W = jnp.ones((8, 384), jnp.float32) * 0.01
        R = jnp.ones((128, 384), jnp.float32) * 0.01
        b = jnp.zeros((384,))
        jax.grad(lambda W: fg.fused_gru_layer(x, h0, W, R, b)[0].sum())(W)
        assert called, "GRU backward kernel was not used in the vjp"

    def test_hidden_tiled_parity(self, rng):
        """nj > 1 (H=256 with a forced 128 tile) — cross-slice dh coupling
        in the backward (the GRU-specific hazard: dh0 and the dh carry mix
        full-H matmul contributions with per-slice direct terms)."""
        import deeplearning4j_tpu.ops.pallas.fused_gru as fg
        from deeplearning4j_tpu.ops.recurrent import gru_layer

        B, T, F, H = 8, 4, 8, 256
        x, h0, W, R, b = self._mk(rng, B, T, F, H, scale=0.05)
        orig_f, orig_b = fg.gru_tile, fg.gru_bwd_tile
        try:
            fg.gru_tile = lambda *a, **k: 128
            fg.gru_bwd_tile = lambda *a, **k: 128
            gk = jax.grad(lambda args: (
                fg.fused_gru_layer(args[0], args[1], W, args[2], b)[0].sum()
                + fg.fused_gru_layer(args[0], args[1], W, args[2],
                                     b)[1].sum()))((x, h0, R))
        finally:
            fg.gru_tile, fg.gru_bwd_tile = orig_f, orig_b
        gs = jax.grad(lambda args: (
            gru_layer(args[0], args[1], W, args[2], b)[0].sum()
            + gru_layer(args[0], args[1], W, args[2], b)[1].sum()))((x, h0, R))
        for name, a, b_ in zip(("x", "h0", "R"), gk, gs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"d{name} tiled")

    @pytest.mark.parametrize("H", [100, 200])
    def test_unaligned_hidden_padding_exact(self, rng, H):
        from deeplearning4j_tpu.ops.pallas import fused_gru_layer
        from deeplearning4j_tpu.ops.recurrent import gru_layer
        B, T, F = 8, 5, 8
        x, h0, W, R, b = self._mk(rng, B, T, F, H)
        ok, hk = fused_gru_layer(x, h0, W, R, b)
        os_, hs = gru_layer(x, h0, W, R, b)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(os_),
                                   rtol=2e-5, atol=2e-6)
        gk = jax.grad(lambda R: fused_gru_layer(x, h0, W, R, b)[0].sum())(R)
        gs = jax.grad(lambda R: gru_layer(x, h0, W, R, b)[0].sum())(R)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gs),
                                   rtol=2e-4, atol=2e-5)

    def test_scan_fallback_flag(self, rng, monkeypatch):
        import deeplearning4j_tpu.ops.pallas.fused_gru as fg
        from deeplearning4j_tpu.common.env import env

        x, h0, W, R, b = self._mk(rng, 8, 4, 8, 128)
        g_kernel = jax.grad(lambda W: fg.fused_gru_layer(
            x, h0, W, R, b)[0].sum())(W)
        monkeypatch.setenv("DL4J_TPU_GRU_SCAN_BWD", "1")
        env.reload()
        try:
            g_scan = jax.grad(lambda W: fg.fused_gru_layer(
                x, h0, W, R, b)[0].sum())(W)
        finally:
            monkeypatch.delenv("DL4J_TPU_GRU_SCAN_BWD")
            env.reload()
        np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_scan),
                                   rtol=2e-4, atol=2e-5)

    def test_registry_selection(self, rng):
        """The gru_layer op routes through the kernel in its selected regime
        (one tile spans H) and stays on the scan for multi-tile shapes."""
        from deeplearning4j_tpu.ops.pallas.fused_gru import (_gru_applicable,
                                                             gru_tile)

        x = jnp.zeros((64, 8, 32))
        h0 = jnp.zeros((64, 256))
        W = jnp.zeros((32, 768))
        R = jnp.zeros((256, 768))
        b = jnp.zeros((768,))
        assert _gru_applicable(x, h0, W, R, b)
        # big B*H where even the largest fitting tile < H: not applicable
        xb = jnp.zeros((256, 8, 32))
        hb_ = jnp.zeros((256, 2048))
        Wb = jnp.zeros((32, 6144))
        Rb = jnp.zeros((2048, 6144))
        bb = jnp.zeros((6144,))
        if gru_tile(256, 2048, save_residuals=True) != 2048:
            assert not _gru_applicable(xb, hb_, Wb, Rb, bb)

    def test_gru_layer_class_reaches_kernel(self, rng, monkeypatch):
        """End-to-end: the nn GRU layer's op("gru_layer") dispatch selects
        the Pallas impl for an aligned shape."""
        import deeplearning4j_tpu.ops.pallas.fused_gru as fg

        called = []
        orig = fg._fused_gru_recurrence

        def spy(*a, **kw):
            called.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(fg, "_fused_gru_recurrence", spy)
        from deeplearning4j_tpu.ops import get_op
        x = jnp.asarray(rng.normal(size=(8, 4, 16)).astype(np.float32))
        h0 = jnp.zeros((8, 128))
        W = jnp.asarray(rng.normal(size=(16, 384)).astype(np.float32) * 0.1)
        R = jnp.asarray(rng.normal(size=(128, 384)).astype(np.float32) * 0.1)
        b = jnp.zeros((384,))
        get_op("gru_layer")(x, h0, W, R, b)
        assert called, "registry did not route gru_layer to the kernel"
