"""The parts ``zoo.hybrid_linear_moe_lm`` added: Kimi Delta Attention's
chunk form (``ops/kda_chunk.py``) against the recurrence one position at
a time, its kernel pair in the interpreter, the layer against the
plain reference of ``benchmarks/references/ling3flash_ep64share.py``,
the convolution without bias, selection of experts by group, latent
attention without a query bottleneck and with a value narrower than its
query (padded for the flash pair), and the whole stack's loss and
gradients against the reference."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.nn.layers import (
    KimiDeltaAttention,
    LatentAttention,
    RoutedExperts,
)
from deeplearning4j_tpu.nn.layers.state_space import causal_conv_silu
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import dispatch, kda_chunk as kda

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIGHEST = jax.lax.Precision.HIGHEST


def _ref():
    from benchmarks.harness.spec import load_module

    return load_module("references", "ling3flash_ep64share")


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-12)


def _recurrence(q, k, v, g, beta):
    """The delta rule one position at a time, ``[b, t, h, d]``, q and k
    raw: each row over its L2 norm (eps 1e-6 under the root), q also
    over √d, as ``kda_chunk``'s contract has it."""
    unit = lambda x: x / jnp.sqrt(  # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / np.sqrt(q.shape[-1]), unit(k)

    def step(st, per):
        qt, kt, vt, gt, bt = per
        st = st * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", kt, st, precision=HIGHEST)
        st = st + bt[..., None, None] * kt[..., None] * (vt - seen)[
            ..., None, :]
        return st, jnp.einsum("bhkv,bhk->bhv", st, qt, precision=HIGHEST)

    b, _, h, d = q.shape
    per = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, v.shape[-1])), per)
    return jnp.moveaxis(o, 0, 1)


# ranges of the raw rows' norms of q and k: about unit, thirty times
# that, and near nought, where the norm's eps under the root counts
NORMS = {"unit": (0.5, 2.0), "x30": (15.0, 60.0), "tiny": (1e-4, 1e-2)}


def _inputs(b, t, h, d, lower, seed=0, dtype=jnp.float32, norms=None):
    """Keys (with a common part, as SiLU outputs have) and queries as
    ``kda_chunk`` takes them, raw rows whose norms are log-uniform over
    ``norms``; with ``norms`` None as the per-chunk functions take them,
    unit keys and queries over √d. Log-decays in (lower, 0) crowding
    ``lower``'s end when it is large."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(rng.normal(size=(b, t, h, d)))
    k = unit(rng.normal(size=(b, t, h, d)) + 0.4)
    v = rng.normal(size=(b, t, h, d))
    g = lower * rng.uniform(0.0, 1.0, (b, t, h, d)) ** 0.25
    beta = rng.uniform(0.0, 1.0, (b, t, h))
    if norms is None:
        q = q / np.sqrt(d)
    else:
        lo, hi = np.log(norms)
        q, k = (x * np.exp(rng.uniform(lo, hi, (b, t, h, 1))) for x in (q, k))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


@pytest.fixture()
def kernels_on(monkeypatch):
    """``DL4J_TPU_PALLAS=1``: the kernel runs in the interpreter here."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()
    yield
    dispatch.reset_for_tests()


@pytest.mark.parametrize("t, chunk, lower", [
    (64, 64, -5.0), (80, 32, -0.01), (37, 16, -4.999), (96, 64, -2.0)],
    ids=["t64-c64-bound", "t80-c32-near0", "t37-c16-bound", "t96-c64"])
def test_chunk_form_is_the_recurrence_in_value_and_gradient(t, chunk,
                                                            lower):
    """``kda_chunk`` in XLA (dispatch off here) against the recurrence,
    both normalising raw q and k: the output and every input's gradient,
    at lengths the chunk does not divide and decays at the lower bound
    and near nought."""
    args = _inputs(2, t, 2, 16, lower, norms=NORMS["unit"])
    probe = jax.random.normal(jax.random.PRNGKey(1), (2, t, 2, 16))
    want, want_vjp = jax.vjp(_recurrence, *args)
    got, got_vjp = jax.vjp(lambda *a: kda.kda_chunk(*a, chunk), *args)
    assert _close(got, want, 2e-5)
    for name, a, b in zip("qkvgb", got_vjp(probe), want_vjp(probe)):
        assert _close(a, b, 1e-4), name


def test_unit_lower_inverse_is_the_inverse():
    """The product form over blocks of 16, blocks of blocks after."""
    rng = np.random.default_rng(3)
    for c in (16, 48, 64):
        n = np.tril(rng.uniform(-0.3, 0.6, (c, c)), -1).astype(np.float32)
        got = kda.unit_lower_inverse(jnp.asarray(n))
        want = np.linalg.inv(np.eye(c) + n.astype(np.float64))
        assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(
            want).max(), c


def test_chunk_backward_is_autodiff_of_the_chunk():
    """``chunk_bwd`` (what the backward kernel computes for one chunk)
    against autodiff of ``chunk_prep`` then ``chunk_step``, at decays at
    the lower bound and near nought."""
    rng = np.random.default_rng(1)
    c, d = 64, 32
    q, k, v, _, _ = _inputs(1, c, 1, d, -1.0, seed=5)
    q, k, v = (a[0, :, 0] for a in (q, k, v))
    for lower in (-5.0, -0.05):
        g = lower * rng.uniform(0.0, 1.0, (c, d)) ** 0.25
        gam = jnp.cumsum(jnp.asarray(g, jnp.float32), axis=0)
        beta = jnp.asarray(rng.uniform(0.0, 1.0, (c, 1)), jnp.float32)
        st, dst = (jnp.asarray(rng.normal(size=(d, d)), jnp.float32)
                   for _ in range(2))
        do = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)

        def chunk(*a):
            st_next, o = kda.chunk_step(
                a[5], *kda.chunk_prep(*a[:5], jnp.float32), jnp.float32)
            return o, st_next

        _, vjp = jax.vjp(chunk, q, k, v, gam, beta, st)
        got = kda.chunk_bwd(q, k, v, gam, beta, st, do, dst, jnp.float32)
        for name, a, b in zip(("dq", "dk", "dv", "dgam", "dbeta", "dst"),
                              got, vjp((do, dst))):
            assert _close(a, b, 1e-5), (lower, name)


def _decays(lower, shape, rng):
    """Log-decays in (lower, 0) crowding ``lower``'s end; ``lower`` None
    mixes decays at the -5 bound with decays near nought."""
    if lower is not None:
        return lower * rng.uniform(0.0, 1.0, shape) ** 0.25
    return np.where(rng.uniform(size=shape) < 0.5,
                    -5.0 * rng.uniform(0.9, 1.0, shape),
                    -1e-3 * rng.uniform(size=shape))


@pytest.mark.parametrize("lower", [-5.0, -0.01, None],
                         ids=["bound", "near0", "mixed"])
def test_chunk_sums_are_the_cumulative_sums(lower):
    """``chunk_sums`` (Γ as the kernels sum it) against ``chunk_cumsum``
    and ``chunk_sums_t`` against its transpose, chunk by chunk, each to
    float32 rounding of the largest magnitude (8 ε: the log2 of the
    chunk of 64, and 2)."""
    rng = np.random.default_rng(7)
    b, nc, c, n = 2, 3, 64, 256
    g = jnp.asarray(_decays(lower, (b, nc * c, n), rng), jnp.float32)
    dgam = jnp.asarray(rng.normal(size=(b, nc * c, n)), jnp.float32)
    by_chunk = jax.vmap(jax.vmap(kda.chunk_sums))
    by_chunk_t = jax.vmap(jax.vmap(kda.chunk_sums_t))
    want, vjp = jax.vjp(lambda x: kda.chunk_cumsum(x, c), g)
    got = by_chunk(g.reshape(b, nc, c, n)).reshape(g.shape)
    got_t = by_chunk_t(dgam.reshape(b, nc, c, n)).reshape(g.shape)
    eps = 8 * np.finfo(np.float32).eps
    assert _close(got, want, eps)
    assert _close(got_t, vjp(dgam)[0], eps)
    # the last row of each chunk is the whole chunk's sum, the first of
    # the transpose's likewise
    last = np.asarray(got).reshape(b, nc, c, n)[:, :, -1]
    whole = np.asarray(g, np.float64).reshape(b, nc, c, n).sum(axis=2)
    assert _close(last, whole, eps)
    first = np.asarray(got_t).reshape(b, nc, c, n)[:, :, 0]
    assert _close(first, np.asarray(dgam, np.float64).reshape(
        b, nc, c, n).sum(axis=2), eps)


def _kernel_sums(g, chunk):
    """``chunk_cumsum`` as the kernels sum it (``chunk_sums`` a chunk
    at a time), for the XLA form beside them."""
    b, t = g.shape[:2]
    x = g.reshape((b, t // chunk, chunk, -1))
    return jax.vmap(jax.vmap(kda.chunk_sums))(x).reshape(g.shape)


def _group_calls():
    fam = default_registry().get("kda_chunk_group_calls_total")
    return {} if fam is None else {
        "/".join(c.label_values): c.value for c in fam.children()}


@pytest.mark.parametrize("dtype, t, h, norms", [
    (jnp.float32, 100, 2, "unit"), (jnp.bfloat16, 64, 2, "unit"),
    (jnp.float32, 64, 3, "x30"), (jnp.bfloat16, 100, 3, "x30"),
    (jnp.float32, 100, 4, "tiny"), (jnp.bfloat16, 64, 4, "tiny")],
    ids=["f32-t100", "bf16-t64", "f32-t64-h3-x30", "bf16-t100-h3-x30",
         "f32-t100-h4-tiny", "bf16-t64-h4-tiny"])
def test_kernel_pair_in_the_interpreter(kernels_on, dtype, t, h, norms):
    """The Pallas pair (grids over row, head group and chunk, the states
    and their cotangents in scratch, the backward walking the chunks
    last first from the states the forward kept, raw q and k normalised
    and their gradients taken back through the norms, Γ summed from the
    raw log-decays and dΓ summed back into dg inside the kernels) gives
    the XLA form's output and gradients, the norms there in XLA, on rows
    of about unit norm, thirty times it and near nought (where the
    norm's eps counts), padded rows of nought where the chunk does not
    divide the length: to its tolerances where the XLA
    form sums Γ as the kernels do, and in float32 within the chunk
    form's tolerances against the recurrence where it takes
    ``chunk_cumsum``'s; with every head a group, as the shape picks it,
    it gives one head a program's to the bit; both routings and the
    group size are counted."""
    args = _inputs(1, t, h, 128, -5.0, seed=4, dtype=dtype,
                   norms=NORMS[norms])
    reg = default_registry()
    fam = lambda: {  # noqa: E731
        "/".join(c.label_values): c.value
        for c in reg.get("pallas_dispatch_total").children()}
    before = fam() if reg.get("pallas_dispatch_total") else {}
    groups_before = _group_calls()
    probe = jax.random.normal(jax.random.PRNGKey(2), (1, t, h, 128), dtype)
    got, got_vjp = jax.vjp(lambda *a: kda.kda_chunk(*a, 64), *args)
    grads = got_vjp(probe)
    after = fam()
    for kernel in ("kda_chunk_fwd", "kda_chunk_bwd"):
        assert after.get(f"{kernel}/interpret", 0) == before.get(
            f"{kernel}/interpret", 0) + 1, kernel
        key = f"{kernel}/{h}"
        assert _group_calls().get(key, 0) == groups_before.get(key, 0) + 1
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda.tiling, "kda_chunk_heads", lambda *a, **k: 1)
        one, one_vjp = jax.vjp(lambda *a: kda.kda_chunk(*a, 64), *args)
        for name, a, b in zip("oqkvgb", (got,) + grads,
                              (one,) + one_vjp(probe)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
        mp.setenv("DL4J_TPU_PALLAS", "0")
        dispatch.reset_for_tests()
        summed, summed_vjp = jax.vjp(lambda *a: kda.kda_chunk(*a, 64),
                                     *args)
        wide = [a.astype(jnp.float32) for a in args]
        _, exact_vjp = jax.vjp(lambda *a: kda.kda_chunk(*a, 64), *wide)
        mp.setattr(kda, "chunk_cumsum", _kernel_sums)
        want, want_vjp = jax.vjp(lambda *a: kda.kda_chunk(*a, 64), *args)
    assert _close(got, want, 1e-6)
    if dtype == jnp.float32:
        assert _close(got, summed, 2e-5)
        for name, a, b in zip("qkvgb", grads, summed_vjp(probe)):
            assert _close(a, b, 1e-4), name
    exact = _recurrence(*wide)
    assert _close(got, exact, 2e-5 if dtype == jnp.float32 else 3e-2)
    err = lambda a, b: float(  # noqa: E731
        np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
        / np.abs(np.asarray(b, np.float32)).max())
    for name, a, b, c in zip("qkvgb", grads, want_vjp(probe),
                             exact_vjp(probe.astype(jnp.float32))):
        assert a.dtype == b.dtype, name
        if dtype == jnp.float32:
            assert err(a, b) < 1e-5, name
        else:
            # in bfloat16 both forms round the same products: the
            # kernel's gradients lie as near float32's as autodiff's
            assert err(a, c) <= 1.25 * err(b, c) + 1e-3, name


# (h, d, itemsize, forward's heads, backward's heads): ling3flash.fit_4k's
# class, a head of 256, a count of heads with no divisor under the lane
# cap but itself, float32
HEAD_GROUPS = [(32, 128, 2, 4, 4), (32, 256, 2, 2, 2), (5, 128, 2, 1, 1),
               (6, 128, 4, 3, 3), (12, 256, 4, 2, 2)]


@pytest.mark.parametrize("h, d, itemsize, fwd, bwd", HEAD_GROUPS,
                         ids=[f"h{c[0]}-d{c[1]}-i{c[2]}" for c in HEAD_GROUPS])
def test_kernel_pair_groups_heads_by_shape(h, d, itemsize, fwd, bwd):
    """``tiling.kda_chunk_heads``: the largest divisor of h within the
    lane cap whose residents fit the budget, for each kernel; every pick
    within ``VMEM_BUDGET_BYTES``, and a group of every head only where
    its residents fit too."""
    from deeplearning4j_tpu.ops import tiling

    for is_bwd, want in ((False, fwd), (True, bwd)):
        g = tiling.kda_chunk_heads(h, d, 64, itemsize, is_bwd)
        assert g == want and h % g == 0 and g * d <= tiling.KDA_CHUNK_LANES
        assert tiling.kda_chunk_bytes(h, d, 64, itemsize, g, is_bwd) <= (
            tiling.VMEM_BUDGET_BYTES)
        if h * d <= tiling.KDA_CHUNK_LANES:
            assert g == h


def test_kernel_gate_admits_whole_heads_and_chunks():
    ok = kda.kda_chunk_ok
    assert ok((2, 4096, 32, 128), jnp.bfloat16, 64)
    assert ok((1, 100, 2, 128), jnp.float32, 64)
    assert not ok((2, 4096, 32, 64), jnp.bfloat16, 64)     # half a lane tile
    assert not ok((2, 4096, 32, 128), jnp.bfloat16, 40)    # not whole blocks
    assert not ok((2, 4096, 32, 128), jnp.float16, 64)


@pytest.mark.parametrize("counter", [
    "kda_chunk_decay_sums_total", "kda_chunk_qk_norms_total"],
    ids=["decay-sums", "qk-norms"])
@pytest.mark.parametrize("head, where", [(128, "kernel"), (16, "xla")],
                         ids=["head128-kernel", "head16-xla"])
def test_decay_sums_are_counted_by_where(kernels_on, head, where, counter):
    """``kda_chunk_decay_sums_total{where}`` and
    ``kda_chunk_qk_norms_total{where}``: the configuration's stack (six
    delta-rule layers) traced with its gradient counts six, all in the
    kernels where the head fills whole lane tiles and the chunk is the
    cell's 64, all in XLA on the fallback (the tiny head of 16)."""
    from benchmarks.harness.spec import load_module

    cfg = _cfg()
    driver = load_module("drivers", "fit_tokens")
    kwargs = {k: driver.lookup(cfg, path)
              for k, path in cfg["program"]["args"].items()}
    kwargs.update(cfg["program"]["kwargs"])
    kwargs.update(kda_head_dim=head, kda_chunk=64)
    net = MultiLayerNetwork(zoo.hybrid_linear_moe_lm(
        **kwargs, updater="ADAM", learning_rate=1e-3, seed=3))
    params = jax.eval_shape(lambda: net.init().params)
    t = cfg["input"]["length"]
    x = jax.ShapeDtypeStruct((2, t), jnp.float32)

    def sums():
        fam = default_registry().get(counter)
        return {} if fam is None else {
            c.label_values[0]: c.value for c in fam.children()}

    before = sums()
    jax.eval_shape(jax.grad(lambda p, x_, y_: net._score_pure(
        p, net.state, x_, y_, None, None, train=True)[0]), params, x, x)
    after = sums()
    other = "xla" if where == "kernel" else "kernel"
    assert after.get(where, 0) - before.get(where, 0) == 6, after
    assert after.get(other, 0) == before.get(other, 0), after


def test_chunk_calls_are_counted():
    reg = default_registry()
    args = _inputs(1, 40, 2, 16, -1.0)
    kda.kda_chunk(*args, 16)
    fam = reg.get("kda_chunk_calls_total")
    counted = {"/".join(c.label_values): c.value for c in fam.children()}
    assert counted.get("16/3", 0) >= 1


def _cfg():
    with open(os.path.join(
            REPO, "benchmarks/configs/ling3flash_ep64share.json")) as f:
        cfg = json.load(f)
    return {**{k: v for k, v in cfg.items() if k != "tiny"}, **cfg["tiny"]}


def test_delta_rule_layer_is_the_references_row():
    """``KimiDeltaAttention.apply`` on the reference's leaves against the
    reference's mixer (the recurrence), row by row, in float32."""
    ref, cfg = _ref(), _cfg()
    s = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.PRNGKey(7))
    p = {k: v for k, v in params["1"].items()
         if k not in ("attn_norm", "ffn_norm", "Wg", "Wu", "Wd")}
    layer = KimiDeltaAttention(n_in=s["d"], n_heads=s["h"],
                               head_dim=s["dk"], chunk=16, eps=s["eps"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, s["d"]))
    got, _ = layer.apply(p, x, {})
    mm = lambda a, w: jnp.matmul(a, w, precision=HIGHEST)  # noqa: E731
    want = jnp.stack([ref._kda(s, p, row, mm, ref._exact) for row in x])
    assert _close(got, want, 1e-4)


def test_convolution_without_bias_is_a_bias_of_nought(kernels_on):
    """No bias: the forward and the gradients of a zero bias, on XLA's
    path and on the backward kernel's (interpreter)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 160, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    zero = jnp.zeros((32,))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    got, got_vjp = jax.vjp(lambda a, b: causal_conv_silu(a, b), x, w)
    want, want_vjp = jax.vjp(lambda a, b: causal_conv_silu(a, b, zero), x, w)
    assert _close(got, want, 1e-6)
    for a, b in zip(got_vjp(probe), want_vjp(probe)):
        assert _close(a, b, 1e-5)


def _grouped(n_group=4, topk_group=2, **kw):
    return RoutedExperts(n_in=16, hidden_size=8, n_experts=16, top_k=3,
                         n_shared=0, n_group=n_group, topk_group=topk_group,
                         **kw)


def test_grouped_selection_is_the_brute_force_choice():
    """Every token: the groups whose two best scores sum largest, and
    the top 3 experts among them, by sorting each token's numbers."""
    layer = _grouped()
    params = layer.init_params(jax.random.PRNGKey(0))
    params["router"] = params["router"] * 30
    tokens = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    bias = jax.random.normal(jax.random.PRNGKey(2), (16,)) * 0.1
    chosen, w = layer.route(params, tokens, bias)
    s = np.asarray(jax.nn.sigmoid(tokens @ params["router"]))
    sel = s + np.asarray(bias)
    for n in range(64):
        groups = sel[n].reshape(4, 4)
        best = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = set(np.argsort(-best)[:2])
        allowed = [e for e in range(16) if e // 4 in kept]
        want = sorted(allowed, key=lambda e: -sel[n, e])[:3]
        assert sorted(np.asarray(chosen[n]).tolist()) == sorted(want)
        np.testing.assert_allclose(
            np.asarray(w[n]), s[n, np.asarray(chosen[n])]
            / s[n, np.asarray(chosen[n])].sum(), rtol=1e-5)


def test_one_group_is_the_ungrouped_routing():
    """``n_group`` 1 traces the program it traced before the option."""
    one = _grouped(n_group=1, topk_group=1)
    plain = RoutedExperts(n_in=16, hidden_size=8, n_experts=16, top_k=3,
                          n_shared=0)
    params = one.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    jaxpr = lambda layer: str(jax.make_jaxpr(  # noqa: E731
        lambda p, a: layer.apply(p, a, layer.init_state())[0])(params, x))
    assert jaxpr(one) == jaxpr(plain)


def test_grouped_shares_add_up_to_the_uncut_layer():
    """Under grouped selection the four shares of 4 experts, plus the
    shared expert once, are the uncut layer's output and input
    gradient."""
    kw = dict(n_in=16, hidden_size=8, n_experts=16, top_k=3, scaling=2.5,
              n_group=4, topk_group=2)
    whole = RoutedExperts(n_shared=1, **kw)
    params = {k: v * 4 for k, v in whole.init_params(
        jax.random.PRNGKey(0)).items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 16))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def run(layer, p, fn=None):
        fn = fn or (lambda a: layer.apply(p, a, layer.init_state())[0])
        out, vjp = jax.vjp(fn, x)
        return out, vjp(probe)[0]

    full_out, full_dx = run(whole, params)
    total_out, total_dx = run(None, None, lambda a: (
        jax.nn.silu(a @ params["Sg"]) * (a @ params["Su"])) @ params["Sd"])
    for first in (0, 4, 8, 12):
        share = RoutedExperts(n_shared=0, held_first=first,
                              held_last=first + 3, **kw)
        p = {"router": params["router"],
             **{k: params[k][first:first + 4] for k in ("Eg", "Eu", "Ed")}}
        out, dx = run(share, p)
        total_out, total_dx = total_out + out, total_dx + dx
    assert _close(total_out, full_out, 1e-5)
    assert _close(total_dx, full_dx, 1e-5)


def test_latent_attention_padded_is_the_unpadded_reference(monkeypatch):
    """No query bottleneck, a head norm, query and key 24 wide and
    values 16 (padded to 128 for ``ops.mha``, the query scaled), a gate
    a head: the layer against the reference's latent attention at its
    own widths, row by row, forward and input gradient."""
    monkeypatch.setattr(dispatch, "effective_platform", lambda: "cpu")
    ref, cfg = _ref(), _cfg()
    s = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.PRNGKey(9))
    p = {k: v for k, v in params["5"].items() if k in (
        "Wq", "q_head_norm", "Wkva", "kv_norm", "Wkvb", "Wo", "Wga")}
    p["q_head_norm"] = p["q_head_norm"] * 1.5
    layer = LatentAttention(
        n_in=s["d"], n_heads=s["h"], kv_rank=s["kvr"], nope_dim=s["nope"],
        rope_dim=s["rope"], v_dim=s["vd"], rope_theta=s["theta"],
        eps=s["eps"], q_head_norm=True, head_gate=True)
    assert layer._width() == 128
    assert set(layer.init_params(jax.random.PRNGKey(0))) == set(p)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, s["d"]))
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    got, got_vjp = jax.vjp(lambda a: layer.apply(p, a, {})[0], x)
    mm = lambda a, w: jnp.matmul(a, w, precision=HIGHEST)  # noqa: E731
    want, want_vjp = jax.vjp(lambda a: jax.vmap(
        lambda row: ref._mla(s, p, row, mm, ref._exact))(a), x)
    assert _close(got, want, 1e-4)
    assert _close(got_vjp(probe)[0], want_vjp(probe)[0], 1e-4)


def test_stack_matches_the_reference_loss_and_gradients():
    """The zoo's stack at the configuration's tiny sizes: the step
    program's loss and every leaf's gradient against the reference, in
    float32; the layer pattern is the configuration's (latent attention
    at layer 5, a dense MLP at layer 1 only)."""
    from benchmarks.harness.spec import load_module

    ref, cfg = _ref(), _cfg()
    driver = load_module("drivers", "fit_tokens")
    kwargs = {k: driver.lookup(cfg, path)
              for k, path in cfg["program"]["args"].items()}
    kwargs.update(cfg["program"]["kwargs"])
    kwargs.update(dtype="float32", compute_dtype=None)
    net = MultiLayerNetwork(zoo.hybrid_linear_moe_lm(
        **kwargs, updater="ADAM", learning_rate=1e-3, seed=3))
    kinds = [type(layer.attention).__name__ for layer in net.conf.layers[1:-1]]
    assert kinds == ["KimiDeltaAttention"] * 4 + ["LatentAttention"] + [
        "KimiDeltaAttention"] * 2
    ffns = [type(layer.ffn).__name__ for layer in net.conf.layers[1:-1]]
    assert ffns == ["GatedFeedForward"] + ["RoutedExperts"] * 6
    params, ref_state = ref.init(cfg, jax.random.PRNGKey(5))
    net.init(params=jax.tree.map(jnp.copy, params))
    rng = np.random.default_rng(0)
    t = cfg["input"]["length"]
    ids = rng.integers(0, cfg["vocab_size"], (2, t + 1))
    x = jnp.asarray(ids[:, :t], jnp.float32)
    y = jnp.asarray(ids[:, 1:], jnp.float32)
    (want, _), grads = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, ref_state, x, y), has_aux=True)(params)
    (got, _), got_grads = jax.value_and_grad(
        lambda p: net._score_pure(p, net.state, x, y, None, None,
                                  train=True), has_aux=True)(params)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    for layer, leaves in grads.items():
        assert set(leaves) == set(got_grads[layer]), layer
        for name, g in leaves.items():
            assert _close(got_grads[layer][name], g, 2e-4), (layer, name)
