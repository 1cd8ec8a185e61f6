"""Pallas kernel correctness vs the XLA reference implementations
(the backend-vs-backend consistency strategy of SURVEY.md §4 —
``TestConvolution`` compared cuDNN helper vs builtin; here the Pallas
kernels run in interpret mode on CPU against the jnp reference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import kernel_tols, pallas_interpret
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops.flash_attention import flash_attention
from deeplearning4j_tpu.ops.lstm_cell import _reference_cell, lstm_cell
from deeplearning4j_tpu.parallel.sequence import attention


# (heads, head size): two heads a program, the same with one group,
# one head a program, four heads a program
HEADS = [(8, 64), (2, 64), (4, 128), (16, 32)]


def _reference(q, k, v, n_heads, causal):
    """Plain attention on ``[b, t, h*d]`` arrays, head i in columns
    ``[i*d, (i+1)*d)``: moved to ``[b, h, t, d]`` and back around the
    reference, every product at the highest precision."""
    b, t, f = q.shape
    d = f // n_heads

    def heads(a):
        return jnp.transpose(a.reshape(b, t, n_heads, d), (0, 2, 1, 3))

    with jax.default_matmul_precision("highest"):
        o = attention(heads(q), heads(k), heads(v), causal=causal)
    return jnp.transpose(o, (0, 2, 1, 3)).reshape(b, t, f)


def _qkvg(b, t, f, dtype, seed):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, f), dtype) for _ in range(4))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,d", HEADS)
    def test_matches_reference(self, causal, h, d):
        q, k, v, _ = _qkvg(2, 64, h * d, jnp.float32, 0)
        out = flash_attention(q, k, v, h, causal=causal, block_q=32,
                              block_k=32, interpret=pallas_interpret())
        ref = _reference(q, k, v, h, causal)
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=rtol, atol=atol
        )

    def test_single_block(self):
        q, k, v, _ = _qkvg(1, 16, 2 * 64, jnp.float32, 1)
        out = flash_attention(q, k, v, 2, causal=True,
                              interpret=pallas_interpret())
        ref = _reference(q, k, v, 2, True)
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=rtol, atol=atol
        )

    def test_indivisible_length_raises(self):
        q = jnp.zeros((1, 100, 128))
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, q, q, 2, block_q=64, block_k=64,
                            interpret=True)

    @pytest.mark.parametrize("h,d", [(3, 16), (2, 48), (3, 64)])
    def test_heads_that_fill_no_column_block_raise(self, h, d):
        """The kernels' own entry refuses what ``mha`` routes to XLA:
        a head size that neither divides 128 nor is a multiple of it,
        and a head count that is no whole number of groups."""
        from deeplearning4j_tpu.ops import tiling

        assert tiling.attention_heads_per_program(h, d) is None
        q = jnp.zeros((1, 128, h * d))
        with pytest.raises(ValueError, match="column block"):
            flash_attention(q, q, q, h, interpret=True)


def _flash_module():
    import importlib

    # the ops package re-exports the function under the module's
    # name, so import the MODULE via importlib
    return importlib.import_module("deeplearning4j_tpu.ops.flash_attention")


def _dispatch_counts(kernel):
    from deeplearning4j_tpu.observability.metrics import default_registry

    family = default_registry().get("pallas_dispatch_total")
    return {
        m: 0 if family is None
        else int(family.labels(kernel=kernel, mode=m).value)
        for m in ("pallas", "xla", "interpret")
    }


class TestFlashPair:
    """The differentiated path on ``[b, t, h*d]`` arrays: the forward
    kernel hands out each row's logsumexp and the fused backward
    kernel rebuilds the probabilities from it, tile by tile."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("t,block_q,block_k", [
        (128, 32, 64),    # unequal blocks, static loops
        (128, 64, 32),    # the other way
        (512, 512, 512),  # what mha picks at the benchmark's class
        (512, 64, 128),   # more than four blocks: fori loops
    ])
    def test_grads_match_reference(self, causal, dtype, t, block_q,
                                   block_k):
        fa = _flash_module()
        h = 2                               # d = 64: one group of two
        q, k, v, g = _qkvg(1, t, h * 64, dtype, 11)
        f32 = lambda a: a.astype(jnp.float32)

        out, vjp = jax.vjp(
            lambda *a: fa._flash_diff(*a, h, causal, True, block_q,
                                      block_k), q, k, v)
        ref, vjp_ref = jax.vjp(
            lambda *a: _reference(*a, h, causal), f32(q), f32(k), f32(v))
        # float32: the kernel and the reference agree to rounding;
        # bfloat16: operands of every product are rounded to 8 bits,
        # gradients chain three products deep
        rtol, atol = ((2e-4, 1e-4) if dtype == "float32"
                      else (5e-2, 5e-2))
        for got, want in zip((out,) + vjp(g), (ref,) + vjp_ref(f32(g))):
            assert got.dtype == jnp.dtype(dtype)
            assert got.shape == (1, t, h * 64)
            np.testing.assert_allclose(np.asarray(f32(got)),
                                       np.asarray(want), rtol=rtol,
                                       atol=atol)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("schedule", ["resident", "streamed"])
    @pytest.mark.parametrize("h,d", HEADS)
    def test_every_head_grouping_matches_reference(self, h, d, schedule,
                                                   causal, monkeypatch):
        """Output, dq, dk, dv against float32 attention at the highest
        precision for each way heads share a program's 128 lanes, on
        the resident schedule (the flash pair) and on the streamed one
        (the kernel forward, the blockwise XLA backward)."""
        fa = _flash_module()
        if schedule == "streamed":
            monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 63)
        before = _dispatch_counts("flash_attention_bwd")
        q, k, v, g = _qkvg(2, 128, h * d, jnp.float32, 5)
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda *a: fa._flash_diff(*a, h, causal, True, 64, 32),
                q, k, v)
            got = (out,) + vjp(g)
        ref, vjp_ref = jax.vjp(
            lambda *a: _reference(*a, h, causal), q, k, v)
        after = _dispatch_counts("flash_attention_bwd")
        counted = "interpret" if schedule == "resident" else "xla"
        assert after[counted] == before[counted] + 1
        for a, b_ in zip(got, (ref,) + vjp_ref(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_logsumexp_matches_reference_scores(self, causal):
        fa = _flash_module()
        t, h, d = 128, 4, 32
        q, k, v, _ = _qkvg(1, t, h * d, jnp.float32, 11)
        out, lse = fa.flash_attention(q, k, v, h, causal=causal,
                                      block_q=32, block_k=64,
                                      interpret=True, with_lse=True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(1, t, h, d),
                       k.reshape(1, t, h, d),
                       precision="highest") / d ** 0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e9)
        # a head a row, batch-major: [b*h, 1, t]
        assert lse.shape == (h, 1, t) and lse.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(lse).reshape(1, h, t),
            np.asarray(jax.nn.logsumexp(s, axis=-1)), rtol=1e-5,
            atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_reference(q, k, v, h, causal)),
            rtol=2e-4, atol=2e-5)

    def test_forward_alone_has_one_output(self):
        fa = _flash_module()
        q, k, v, _ = _qkvg(1, 128, 2 * 64, jnp.float32, 11)

        def pallas_calls(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield eqn
                    continue
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from pallas_calls(sub)

        def kernel_outputs(fn):
            calls = list(pallas_calls(jax.make_jaxpr(fn)(q, k, v).jaxpr))
            assert len(calls) == 1
            return [o.aval.shape for o in calls[0].outvars]

        # output() and ModelServer's forward: no logsumexp is written
        assert kernel_outputs(
            lambda *a: fa._flash_diff(*a, 2, True, True)) == [(1, 128, 128)]
        assert kernel_outputs(
            lambda *a: jax.vjp(
                lambda *b: fa._flash_diff(*b, 2, True, True), *a)[0]
        ) == [(1, 128, 128), (2, 1, 128)]

    def test_streamed_forward_hands_out_no_logsumexp(self, monkeypatch):
        fa = _flash_module()
        monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 63)
        q, k, v, _ = _qkvg(1, 128, 2 * 64, jnp.float32, 11)
        with pytest.raises(ValueError, match="logsumexp"):
            fa.flash_attention(q, k, v, 2, interpret=True, with_lse=True)

    def test_dispatch_is_counted_per_traced_call(self, monkeypatch):
        """``mha`` notes ``flash_attention`` once per traced call and
        the backward notes ``flash_attention_bwd`` when it is traced:
        ``pallas`` under the chip's gate, ``xla`` with a key mask and
        for heads that fill no 128-lane column block."""
        fa = _flash_module()
        monkeypatch.setattr(dispatch, "effective_platform", lambda: "tpu")
        monkeypatch.setenv("DL4J_TPU_PALLAS", "auto")
        monkeypatch.setenv("DL4J_TPU_TUNE", "off")
        dispatch.reset_for_tests()
        q = jax.ShapeDtypeStruct((2, 128, 2 * 64), jnp.bfloat16)
        odd = jax.ShapeDtypeStruct((2, 128, 2 * 48), jnp.bfloat16)
        mask = jax.ShapeDtypeStruct((2, 128), jnp.float32)

        def traced(fn, *args):
            before = {n: _dispatch_counts(n) for n in
                      ("flash_attention", "flash_attention_bwd")}
            jax.eval_shape(fn, *args)   # shapes only: nothing lowers
            return {n: {m: c - before[n][m]
                        for m, c in _dispatch_counts(n).items() if
                        c - before[n][m]} for n in before}

        try:
            loss = lambda *a, **kw: jnp.sum(
                fa.mha(*a, 2, causal=True, **kw).astype(jnp.float32))
            assert traced(loss, q, q, q) == {
                "flash_attention": {"pallas": 1},
                "flash_attention_bwd": {}}
            assert traced(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == {
                "flash_attention": {"pallas": 1},
                "flash_attention_bwd": {"pallas": 1}}
            assert traced(
                jax.grad(lambda q_, k_, v_, m_: loss(q_, k_, v_, mask=m_),
                         argnums=(0, 1, 2)), q, q, q, mask) == {
                "flash_attention": {"xla": 1},
                "flash_attention_bwd": {}}
            assert traced(jax.grad(loss, argnums=(0, 1, 2)),
                          odd, odd, odd) == {
                "flash_attention": {"xla": 1},
                "flash_attention_bwd": {}}
        finally:
            monkeypatch.undo()
            dispatch.reset_for_tests()

    @pytest.mark.parametrize("causal", [False, True])
    def test_ineligible_head_size_takes_xla_and_is_right(self, causal,
                                                         monkeypatch):
        """Head size 48 fills no 128-lane block: with the kernels
        forced on, ``mha`` still sends it to XLA's attention, counts
        it there, and output and gradients are the reference's."""
        fa = _flash_module()
        monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
        dispatch.reset_for_tests()
        try:
            h = 2
            q, k, v, g = _qkvg(2, 128, h * 48, jnp.float32, 3)
            before = _dispatch_counts("flash_attention")
            out, vjp = jax.vjp(
                lambda *a: fa.mha(*a, h, causal=causal), q, k, v)
            after = _dispatch_counts("flash_attention")
            assert after["xla"] == before["xla"] + 1
            assert after["interpret"] == before["interpret"]
            ref, vjp_ref = jax.vjp(
                lambda *a: _reference(*a, h, causal), q, k, v)
            for a, b_ in zip((out,) + vjp(g), (ref,) + vjp_ref(g)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                           rtol=2e-4, atol=1e-4)
        finally:
            monkeypatch.undo()
            dispatch.reset_for_tests()


class TestLstmCellKernel:
    @pytest.mark.parametrize("peephole", [False, True])
    def test_matches_reference(self, peephole):
        rng = np.random.RandomState(2)
        b, n = 4, 12
        xproj = jnp.asarray(rng.randn(b, 4 * n), jnp.float32)
        h = jnp.asarray(rng.randn(b, n), jnp.float32)
        c = jnp.asarray(rng.randn(b, n), jnp.float32)
        rw = jnp.asarray(rng.randn(n, 4 * n) * 0.1, jnp.float32)
        peeps = (
            tuple(jnp.asarray(rng.randn(n) * 0.1, jnp.float32)
                  for _ in range(3))
            if peephole else None
        )
        h_new, c_new = lstm_cell(xproj, h, c, rw, peeps, interpret=pallas_interpret())
        ref_peeps = (
            tuple(p.reshape(1, n) for p in peeps) if peeps else None
        )
        h_ref, c_ref = _reference_cell(xproj, h, c, rw, ref_peeps)
        np.testing.assert_allclose(
            np.asarray(h_new), np.asarray(h_ref), rtol=2e-5, atol=2e-6
        )
        np.testing.assert_allclose(
            np.asarray(c_new), np.asarray(c_ref), rtol=2e-5, atol=2e-6
        )


class TestDispatch:
    def test_lstm_trains_with_pallas_forced_off_and_on(self, monkeypatch):
        """The fused path must be a pure drop-in: training curves agree
        between DL4J_TPU_PALLAS=0 and =1 (interpret on CPU)."""
        from deeplearning4j_tpu.datasets.api import DataSet
        from deeplearning4j_tpu.nn.conf import (
            InputType,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.layers import (
            GravesLSTM,
            RnnOutputLayer,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        def run(flag):
            monkeypatch.setenv("DL4J_TPU_PALLAS", flag)
            dispatch.reset_for_tests()  # env is cached once per process
            conf = (
                NeuralNetConfiguration.Builder().seed(3)
                .learning_rate(0.1).updater("SGD").list()
                .layer(GravesLSTM(n_out=8))
                .layer(RnnOutputLayer(n_out=2, loss="MCXENT"))
                .set_input_type(InputType.recurrent(5, 7))
                .build()
            )
            net = MultiLayerNetwork(conf).init()
            rng = np.random.RandomState(0)
            x = rng.rand(4, 5, 7).astype(np.float32)
            y = np.zeros((4, 2, 7), np.float32)
            y[:, 0] = 1.0
            ds = DataSet(features=x, labels=y)
            for _ in range(3):
                net.fit(ds)
            return float(net.score_value)

        s_off = run("0")
        # interpret-mode pallas inside scan is slow; 3 iterations only.
        # On CPU the pallas path requires interpret — patch it on.
        import importlib

        lc = importlib.import_module("deeplearning4j_tpu.ops.lstm_cell")

        orig = lc.lstm_cell
        monkeypatch.setattr(
            lc, "lstm_cell",
            lambda *a, **kw: orig(*a, **{**kw, "interpret": True}),
        )
        s_on = run("1")
        assert s_on == pytest.approx(s_off, rel=1e-4)


class TestStreamedFlashAttention:
    """The HBM-resident K/V schedule (K or V of a program beyond
    ``_RESIDENT_KV_BYTES``): K/V stream through VMEM block-by-block
    with scratch accumulators, a set a head, so single-chip sequence
    length is bounded by HBM, not VMEM."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal, monkeypatch):
        fa = _flash_module()
        # force the streamed schedule at test-size sequences
        monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 63)
        q, k, v, _ = _qkvg(2, 128, 2 * 64, jnp.float32, 4)
        out = fa.flash_attention(
            q, k, v, 2, causal=causal, block_q=32, block_k=32,
            interpret=pallas_interpret(),
        )
        ref = _reference(q, k, v, 2, causal)
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=rtol, atol=atol
        )


class TestBlockwiseBackward:
    """Long-context training path: beyond the VMEM-residency bound the
    forward streams K/V (and hands out no logsumexp), so the custom-vjp
    backward runs blockwise (lax.scan over K/V blocks, no [t, t]
    materialization) and must match the reference attention's
    gradients."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal, monkeypatch):
        fa = _flash_module()
        # K/V beyond the patched residency limit -> the streamed
        # forward and the blockwise branch, fed by the REAL kernel
        # forward (interpret off-TPU) — the D-vector consumes the
        # kernel's own output
        monkeypatch.setattr(fa, "_RESIDENT_KV_BYTES", 63)
        before = _dispatch_counts("flash_attention_bwd")["xla"]
        h = 4
        q, k, v, _ = _qkvg(2, 128, h * 32, jnp.float32, 7)

        def loss_diff(q_, k_, v_):
            return jnp.sum(
                fa._flash_diff(
                    q_, k_, v_, h, causal, pallas_interpret()
                ) ** 2
            )

        def loss_ref(q_, k_, v_):
            return jnp.sum(_reference(q_, k_, v_, h, causal) ** 2)

        g_diff = jax.grad(loss_diff, argnums=(0, 1, 2))(q, k, v)
        g_full = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        assert _dispatch_counts("flash_attention_bwd")["xla"] == before + 1
        rtol0, atol0 = kernel_tols()
        # gradients chain ~3 matmuls deep, so on TPU the MXU's bf16
        # input truncation compounds ~5x past the single-matmul
        # tolerance (observed: 0.06% of elements at ~4e-2 abs)
        for a, b_ in zip(g_diff, g_full):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=rtol0,
                atol=5 * atol0,
            )

        # compare the blockwise backward itself against autodiff of
        # the reference (forward outputs from the reference too, so
        # only the backward differs)
        o_ref, vjp_ref = jax.vjp(
            lambda q_, k_, v_: _reference(q_, k_, v_, h, causal),
            q, k, v,
        )
        g = jnp.ones_like(o_ref)
        dq_ref, dk_ref, dv_ref = vjp_ref(g)
        dq, dk, dv = fa._blockwise_attention_bwd(
            q, k, v, o_ref, g, h, causal, block_k=32
        )
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                                   rtol=rtol, atol=atol)


class TestLstmSequenceKernel:
    """Whole-sequence LSTM kernel (RW resident in VMEM across all
    timesteps — the per-step reload is the HBM roofline that caps the
    scan cell; artifacts/lstm_roofline_r5.md)."""

    def _ref(self, xproj, h0, c0, rw):
        from deeplearning4j_tpu.ops.lstm_cell import _reference_cell

        def cell(carry, xp):
            h, c = carry
            h2, c2 = _reference_cell(xp, h, c, rw, None)
            return (h2, c2), h2

        (hT, cT), hs = jax.lax.scan(cell, (h0, c0), xproj)
        return hs, hT, cT

    def _data(self, T=6, b=8, n=16, dtype=jnp.float32, seed=0):
        rng = np.random.RandomState(seed)
        xp = jnp.asarray(rng.randn(T, b, 4 * n) * 0.3, dtype)
        h0 = jnp.asarray(rng.randn(b, n) * 0.1, dtype)
        c0 = jnp.asarray(rng.randn(b, n) * 0.1, dtype)
        rw = jnp.asarray(rng.randn(n, 4 * n) * 0.2, dtype)
        return xp, h0, c0, rw

    def test_forward_matches_scan(self):
        from deeplearning4j_tpu.ops.lstm_cell import lstm_sequence

        xp, h0, c0, rw = self._data()
        hs_r, hT_r, cT_r = self._ref(xp, h0, c0, rw)
        hs_k, hT_k, cT_k = lstm_sequence(
            xp, h0, c0, rw, pallas_interpret()
        )
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(hs_k, hs_r, rtol=rtol, atol=atol)
        np.testing.assert_allclose(hT_k, hT_r, rtol=rtol, atol=atol)
        np.testing.assert_allclose(cT_k, cT_r, rtol=rtol, atol=atol)

    def test_gradients_match_scan(self):
        from deeplearning4j_tpu.ops.lstm_cell import lstm_sequence

        xp, h0, c0, rw = self._data()
        rng = np.random.RandomState(3)
        ws = jnp.asarray(rng.randn(*xp.shape[:2], rw.shape[0]),
                         xp.dtype)

        def loss(fn, args):
            hs, hT, cT = fn(*args)
            return (jnp.sum(hs * ws) + jnp.sum(hT ** 2)
                    + jnp.sum(cT ** 2))

        g_r = jax.grad(lambda a: loss(self._ref, a))(
            (xp, h0, c0, rw)
        )
        g_k = jax.grad(
            lambda a: loss(
                lambda *x: lstm_sequence(*x, pallas_interpret()), a
            )
        )((xp, h0, c0, rw))
        for name, a, b in zip(("dxproj", "dh0", "dc0", "drw"),
                              g_r, g_k):
            scale = float(jnp.abs(a).max()) + 1e-9
            err = float(jnp.abs(a - b).max()) / scale
            assert err < 5e-4, (name, err)

    def test_size_gate(self):
        from deeplearning4j_tpu.ops.lstm_cell import lstm_sequence_ok

        assert lstm_sequence_ok(1024, 4096, jnp.bfloat16, 256)
        assert not lstm_sequence_ok(2048, 8192, jnp.bfloat16, 256)
        assert not lstm_sequence_ok(16, 128, jnp.float32, 8)  # not 4n
        # a prime batch has one legal block (the whole extent; the
        # chip's compiler refuses a [1, n] block) and it overflows the
        # backward's budget: ineligible, the layer takes the XLA scan
        assert not lstm_sequence_ok(1024, 4096, jnp.bfloat16, 149)
        from deeplearning4j_tpu.ops import tiling

        assert tiling.pick_lstm_batch_block(149, 1024, 4096, 2,
                                            bwd=True) is None
        # a batch with a sublane-multiple divisor shrinks to it
        assert lstm_sequence_ok(1024, 4096, jnp.bfloat16, 152)
        bb = tiling.pick_lstm_batch_block(152, 1024, 4096, 2, bwd=True)
        assert bb is not None and 152 % bb == 0 and bb % 8 == 0

    def test_layer_routes_through_sequence_kernel(self, monkeypatch):
        """GravesLSTM forward equality: DL4J_TPU_PALLAS=1 (sequence
        kernel, interpret on CPU) vs =0 (XLA scan)."""
        import importlib

        # the ops package re-exports a FUNCTION named lstm_cell, which
        # shadows the submodule on attribute access
        lc = importlib.import_module(
            "deeplearning4j_tpu.ops.lstm_cell"
        )
        from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM

        layer = GravesLSTM(n_in=12, n_out=16, peephole=False)
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(
            np.random.RandomState(1).randn(4, 12, 9), jnp.float32
        )
        monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
        dispatch.reset_for_tests()
        y_ref, _ = layer.apply(params, x, {}, train=False)
        monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
        dispatch.reset_for_tests()
        orig = lc.lstm_sequence

        calls = {}

        def spy(xp, h0, c0, rw, interpret=False):
            calls["hit"] = True
            return orig(xp, h0, c0, rw, True)

        monkeypatch.setattr(lc, "lstm_sequence", spy)
        y_k, _ = layer.apply(params, x, {}, train=False)
        assert calls.get("hit"), "sequence kernel was not routed"
        rtol, atol = kernel_tols()
        np.testing.assert_allclose(y_k, y_ref, rtol=rtol, atol=atol)
