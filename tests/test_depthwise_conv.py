"""The Mamba-2 convolution's Pallas backward
(``ops/depthwise_conv.py``, behind ``state_space.causal_conv_silu``)
against autodiff of the shifted float32 products, on the CPU with the
kernel interpreted (``DL4J_TPU_PALLAS=1``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.state_space import (
    causal_conv_silu,
    causal_depthwise_conv,
)
from deeplearning4j_tpu.ops import dispatch, tiling
from deeplearning4j_tpu.ops.depthwise_conv import depthwise_conv_bwd_ok


@pytest.fixture()
def kernels_forced(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()
    yield
    dispatch.reset_for_tests()


def _xla(x, w, bias):
    return jax.nn.silu(causal_depthwise_conv(x, w, bias)).astype(x.dtype)


def _operands(t, c, taps, dtype, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), dtype)
    return (draw(2, t, c), 0.5 * draw(taps, c), draw(c)), draw(2, t, c)


def _counts():
    from deeplearning4j_tpu.observability.metrics import default_registry

    family = default_registry().get("pallas_dispatch_total")
    return {m: 0 if family is None else int(family.labels(
        kernel="depthwise_conv_bwd", mode=m).value)
        for m in ("pallas", "xla", "interpret")}


def _worst(got, want):
    """Largest gap between two arrays over the larger's largest entry."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# a length one time block covers, one that two blocks cover unevenly
# (the rule takes 128-lane tiles: 384 of the 400), one under a halo and
# one shorter than the taps, which the rule refuses
LENGTHS = [256, 400, 100, 3]
# whole 16-row tiles of channels, and a width that has none
WIDTHS = [128, 384, 100]


@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("c", WIDTHS)
@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_output_and_gradients_equal_autodiff_of_the_xla_form(
        kernels_forced, dtype, t, c, taps):
    """Output, ``dx``, ``d conv_W`` and ``d conv_b``: float32 to 1e-5,
    bfloat16 to its rounding; the shapes the rule refuses take
    autodiff itself and are counted as such."""
    args, dy = _operands(t, c, taps, dtype)
    eligible = depthwise_conv_bwd_ok(args[0].shape, args[0].dtype, taps)
    assert eligible == (t >= 128 and c % 16 == 0)
    before = _counts()
    out, vjp = jax.vjp(causal_conv_silu, *args)
    want_out, want_vjp = jax.vjp(_xla, *args)
    after = _counts()
    mode = "interpret" if eligible else "xla"
    assert after[mode] == before[mode] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    assert out.dtype == args[0].dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want_out, np.float32))
    # one bfloat16 step of the largest entry where the sums differ in
    # their order
    limit = 1e-5 if dtype == "float32" else 2 ** -7
    for got, want in zip(vjp(dy), want_vjp(dy)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert _worst(got, want) <= limit


def test_the_block_rule_keeps_whole_tiles_inside_the_budget():
    assert tiling.pick_depthwise_conv_blocks(4096, 4352, 2, 4) == (4096, 128)
    assert tiling.pick_depthwise_conv_blocks(1024, 4352, 2, 4) == (1024, 128)
    assert tiling.pick_depthwise_conv_blocks(400, 384, 4, 2) == (384, 128)
    assert tiling.pick_depthwise_conv_blocks(4096, 48, 2, 4) == (4096, 48)
    for refused in ((100, 128, 2, 4), (256, 100, 2, 4), (256, 128, 2, 129),
                    (256, 0, 2, 4)):
        assert tiling.pick_depthwise_conv_blocks(*refused) is None
    bt, bc = tiling.pick_depthwise_conv_blocks(2 ** 20, 128, 4, 4)
    assert bt % 128 == 0 and tiling._depthwise_conv_bytes(
        bt, bc, 4, 4) <= tiling.VMEM_BUDGET_BYTES


def test_a_position_does_not_move_when_a_later_input_does(kernels_forced):
    """Causal forward; and backward the other way round: ``dx`` at a
    position does not move when an earlier ``dy`` does."""
    (x, w, bias), dy = _operands(300, 32, 4, "float32")
    k = 170
    out, vjp = jax.vjp(causal_conv_silu, x, w, bias)
    later, _ = jax.vjp(causal_conv_silu, x.at[:, k + 1:].add(1.0), w, bias)
    np.testing.assert_array_equal(out[:, :k + 1], later[:, :k + 1])
    assert np.abs(np.asarray(out - later)[:, k + 1:]).max() > 1e-3
    dx, moved = vjp(dy)[0], vjp(dy.at[:, :k].add(1.0))[0]
    np.testing.assert_array_equal(dx[:, k:], moved[:, k:])
    assert np.abs(np.asarray(dx - moved)[:, :k]).max() > 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_remat_around_the_op_gives_the_same_gradients(kernels_forced, dtype):
    args, dy = _operands(256, 32, 4, dtype, seed=3)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                  * dy.astype(jnp.float32))

    plain = jax.grad(loss(causal_conv_silu), (0, 1, 2))(*args)
    remat = jax.grad(loss(jax.checkpoint(causal_conv_silu)), (0, 1, 2))(*args)
    for got, want in zip(remat, plain):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_with_dispatch_off_the_op_is_the_xla_form_under_autodiff(
        monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    dispatch.reset_for_tests()
    try:
        args, dy = _operands(256, 128, 4, "float32")
        before = _counts()
        jaxpr = str(jax.make_jaxpr(
            lambda *a: jax.vjp(causal_conv_silu, *a)[1](dy))(*args))
        assert "pallas_call" not in jaxpr and "custom_vjp" not in jaxpr
        assert _counts()["xla"] == before["xla"] + 1
    finally:
        dispatch.reset_for_tests()
