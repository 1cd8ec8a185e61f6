"""The parts ``zoo.hybrid_conv_moe_lm`` added: the gated short
convolution (``ops/short_conv.py``'s XLA form and its kernel pair in the
interpreter, the layer against the plain reference of
``benchmarks/references/lfm2moe24b_ep8share.py``), grouped-query
attention with q/k norms and rotary positions (and, with its defaults,
the program it traced before the options), routed experts with no
shared expert and an epsilon in the weights' normalisation, one chip's
share of an expert layer against the uncut layer, and the whole stack
against the reference: loss and gradients, and the losses of ``fit()``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.datasets import ListDataSetIterator
from deeplearning4j_tpu.nn.layers import (
    GatedShortConv,
    GroupedQueryAttention,
    RoutedExperts,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import dispatch, short_conv as sc, tiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIGHEST = jax.lax.Precision.HIGHEST


def _ref():
    from benchmarks.harness.spec import load_module

    return load_module("references", "lfm2moe24b_ep8share")


def _cfg():
    with open(os.path.join(
            REPO, "benchmarks/configs/lfm2moe24b_ep8share.json")) as f:
        cfg = json.load(f)
    return {**{k: v for k, v in cfg.items() if k != "tiny"}, **cfg["tiny"]}


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-12)


def _mm(a, w):
    return jnp.matmul(a, w, precision=HIGHEST)


@pytest.fixture()
def kernels_on(monkeypatch):
    """``DL4J_TPU_PALLAS=1``: the kernels run in the interpreter here."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()
    yield
    dispatch.reset_for_tests()


def _dispatched():
    fam = default_registry().get("pallas_dispatch_total")
    return {} if fam is None else {
        "/".join(c.label_values): c.value for c in fam.children()}


def test_short_conv_layer_is_the_references_row():
    """``GatedShortConv.apply`` (the XLA form: dispatch is off on the
    CPU) on the reference's leaves against the reference's mixer, its
    convolution as three shifted products, row by row in float32:
    output, and the gradients of the input and of every leaf. Both are
    float32 sums of the same terms in another order: 1e-5 of the
    largest value."""
    ref, cfg = _ref(), _cfg()
    s = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.PRNGKey(7))
    p = {k: params["1"][k] for k in ("Win", "conv_W", "Wout")}
    layer = GatedShortConv(n_in=s["d"], conv_width=s["taps"])
    assert set(layer.init_params(jax.random.PRNGKey(0))) == set(p)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, s["d"]))
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    got, got_vjp = jax.vjp(lambda p_, a: layer.apply(p_, a, {})[0], p, x)
    want, want_vjp = jax.vjp(lambda p_, a: jax.vmap(
        lambda row: ref._short_conv(s, p_, row, _mm, ref._exact))(a), p, x)
    assert _close(got, want, 1e-5)
    (gp, gx), (wp, wx) = got_vjp(probe), want_vjp(probe)
    assert _close(gx, wx, 1e-5)
    for name in p:
        assert _close(gp[name], wp[name], 1e-5), name


# (b, t, d, taps, dtype, rows a program or None for the picker's): the
# picker's one tile, a length it splits in two, tiles of 16 positions
# (every halo before and behind crossed at each of the four tiles), more
# taps, bfloat16 over tiles and rows
KERNEL_CASES = [
    (2, 48, 256, 3, jnp.float32, None),
    (1, 544, 128, 3, jnp.float32, None),
    (2, 64, 384, 3, jnp.float32, 16),
    (3, 96, 128, 4, jnp.float32, 32),
    (2, 64, 256, 3, jnp.bfloat16, 16),
]


@pytest.mark.parametrize(
    "b, t, d, taps, dtype, rows", KERNEL_CASES,
    ids=["f32-one-tile", "f32-t544-two-tiles", "f32-tiles16",
         "f32-k4-rows3", "bf16-tiles16"])
def test_kernel_pair_in_the_interpreter(kernels_on, monkeypatch, b, t, d,
                                        taps, dtype, rows):
    """The Pallas pair (grid over row and tile of positions, the halo
    before and the look-ahead behind a tile read through BlockSpecs of
    their own, ``dw`` summed over the grid) gives the XLA form's output
    and gradients; both routings are counted. In float32 the two add
    the same terms in the same order up to the compiler's fusing:
    1e-6 of the largest value; in bfloat16 both round the same float32
    values once: 1e-2 (two bfloat16 steps)."""
    if rows:
        monkeypatch.setattr(tiling, "pick_short_conv_rows",
                            lambda *a: rows)
    assert sc.short_conv_ok((b, t, 3 * d), dtype, taps)
    keys = jax.random.split(jax.random.PRNGKey(t + d), 3)
    bcx = jax.random.normal(keys[0], (b, t, 3 * d)).astype(dtype)
    w = jax.random.uniform(keys[1], (taps, d), jnp.float32, -0.6, 0.6)
    probe = jax.random.normal(keys[2], (b, t, d)).astype(dtype)
    before = _dispatched()
    got, got_vjp = jax.vjp(sc.short_conv, bcx, w)
    grads = got_vjp(probe)
    after = _dispatched()
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        assert after.get(f"{kernel}/interpret", 0) == before.get(
            f"{kernel}/interpret", 0) + 1, kernel
    want, want_vjp = jax.vjp(sc.short_conv_xla, bcx, w)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    assert got.dtype == want.dtype and _close(got, want, tol)
    for name, a, b_ in zip(("dbcx", "dw"), grads, want_vjp(probe)):
        assert a.dtype == b_.dtype, name
        assert _close(a, b_, tol if name == "dbcx" else 1e-5), name


def test_kernel_gate_admits_whole_tiles():
    """The cell's class is admitted; channels off the lane tile,
    lengths off the halo, float16 and taps beyond one rolled window are
    not, and the picker's tiles fit the budget at the cell's class."""
    ok = sc.short_conv_ok
    assert ok((2, 4096, 3 * 2048), jnp.bfloat16, 3)
    assert ok((1, 160, 3 * 128), jnp.float32, 9)
    assert not ok((2, 4096, 3 * 2000), jnp.bfloat16, 3)     # d % 128
    assert not ok((2, 4100, 3 * 2048), jnp.bfloat16, 3)     # t % 16
    assert not ok((2, 4096, 3 * 2048), jnp.float16, 3)
    assert not ok((2, 4096, 3 * 2048), jnp.bfloat16, 10)
    assert not ok((2, 4096, 2048 + 1), jnp.bfloat16, 3)
    for bwd, want in ((False, 256), (True, 128)):
        bt = tiling.pick_short_conv_rows(4096, 2048, 2, 3, bwd)
        assert bt == want
        assert tiling.short_conv_bytes(bt, 2048, 2, 3, bwd) <= (
            tiling.VMEM_BUDGET_BYTES)


def _gqa(**kw):
    return GroupedQueryAttention(n_in=64, n_heads=4, n_kv_heads=2,
                                 head_dim=16, **kw)


def test_attention_with_norms_and_rotary_is_the_references_row(
        monkeypatch):
    """q/k head norms and rotary positions over the whole head: the
    layer against the reference's attention (masked softmax with k and
    v repeated), row by row, forward and the gradients of the input and
    the gains, in float32 (1e-4: two float32 softmaxes over 40 keys in
    another order)."""
    monkeypatch.setattr(dispatch, "effective_platform", lambda: "cpu")
    ref, cfg = _ref(), _cfg()
    s = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.PRNGKey(9))
    p = {k: params["2"][k] for k in ("Wq", "Wk", "Wv", "Wo", "q_norm",
                                     "k_norm")}
    p["q_norm"], p["k_norm"] = p["q_norm"] * 1.5, p["k_norm"] * 0.7
    layer = GroupedQueryAttention(
        n_in=s["d"], n_heads=s["h"], n_kv_heads=s["kvh"], head_dim=s["hd"],
        rope_theta=s["theta"], qk_norm=True, eps=s["eps"])
    assert set(layer.init_params(jax.random.PRNGKey(0))) == set(p)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, s["d"]))
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    got, got_vjp = jax.vjp(lambda p_, a: layer.apply(p_, a, {})[0], p, x)
    want, want_vjp = jax.vjp(lambda p_, a: jax.vmap(
        lambda row: ref._attention(s, p_, row, _mm, ref._exact))(a), p, x)
    assert _close(got, want, 1e-4)
    (gp, gx), (wp, wx) = got_vjp(probe), want_vjp(probe)
    assert _close(gx, wx, 1e-4)
    for name in ("q_norm", "k_norm", "Wq", "Wk"):
        assert _close(gp[name], wp[name], 1e-4), name


def _gqa_before_the_options(layer, params, x):
    """``GroupedQueryAttention.apply`` as it stood before rotary
    positions and q/k norms were options: the program granite's cell
    traces."""
    from deeplearning4j_tpu.ops import mha

    b, t, _ = x.shape
    h, kv, hd = layer.n_heads, layer.n_kv_heads, layer.head_dim
    with jax.named_scope("gqa.qkv"):
        q = x @ params["Wq"]
        fold = (layer.scale or hd ** -0.5) * hd ** 0.5
        if fold != 1.0:
            q = q * fold

        def repeated(a):
            return jnp.repeat(a.reshape(b, t, kv, hd), h // kv,
                              axis=2).reshape(b, t, h * hd)

        k, v = repeated(x @ params["Wk"]), repeated(x @ params["Wv"])
    with jax.named_scope("gqa.attention"):
        o = mha(q, k, v, h, causal=True)
    with jax.named_scope("gqa.out"):
        return o @ params["Wo"]


@pytest.mark.parametrize("scale", [0.0, 0.015625], ids=["default", "mult"])
def test_attention_defaults_trace_the_program_before_the_options(scale):
    """With ``rope_theta`` 0 and ``qk_norm`` off the layer traces the
    jaxpr it traced before either option, forward and backward, at
    granite's attention multiplier and at the default scale."""
    layer = _gqa(scale=scale)
    params = layer.init_params(jax.random.PRNGKey(0))
    assert set(params) == {"Wq", "Wk", "Wv", "Wo"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))

    def jaxpr(fn):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, a: fn(p, a).sum(), argnums=(0, 1)))(params, x))

    assert jaxpr(lambda p, a: layer.apply(p, a, {})[0]) == jaxpr(
        lambda p, a: _gqa_before_the_options(layer, p, a))


def test_rotary_alone_and_norm_alone():
    """Each option alone: rotary keeps every position's norm and moves
    position 0 nowhere; the head norm makes each head's RMS its gain."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64)) * 3.0
    for kw in ({"rope_theta": 1e6}, {"qk_norm": True}):
        layer = _gqa(**kw)
        params = layer.init_params(jax.random.PRNGKey(0))
        q = layer._placed(params, x @ params["Wq"], "q_norm", 4)
        plain = (x @ params["Wq"]).reshape(1, 12, 4, 16)
        q = q.reshape(1, 12, 4, 16)
        if "rope_theta" in kw:
            assert _close(q[:, 0], plain[:, 0], 1e-6)
            np.testing.assert_allclose(np.linalg.norm(q, axis=-1),
                                       np.linalg.norm(plain, axis=-1),
                                       rtol=1e-5)
        else:
            np.testing.assert_allclose(
                np.sqrt(np.mean(np.square(q), axis=-1)), 1.0, rtol=1e-3)


def _routed(**kw):
    return RoutedExperts(n_in=16, hidden_size=8, n_experts=16, top_k=4,
                         n_shared=0, **kw)


def test_routed_weights_take_the_epsilon():
    """``topk_eps``: the chosen weights are ``s / (Σ s + eps)``, the
    selection by ``s + route_bias``; with no shared expert the layer
    keeps no shared leaves."""
    layer = _routed(topk_eps=1e-6)
    params = layer.init_params(jax.random.PRNGKey(0))
    assert set(params) == {"router", "Eg", "Eu", "Ed"}
    params["router"] = params["router"] * 30
    tokens = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    bias = jax.random.normal(jax.random.PRNGKey(2), (16,)) * 0.1
    chosen, w = layer.route(params, tokens, bias)
    s = np.asarray(jax.nn.sigmoid(_mm(tokens, params["router"])))
    for n in range(64):
        want = np.argsort(-(s[n] + np.asarray(bias)))[:4]
        assert sorted(np.asarray(chosen[n]).tolist()) == sorted(want)
        pick = s[n, np.asarray(chosen[n])]
        np.testing.assert_allclose(np.asarray(w[n]),
                                   pick / (pick.sum() + 1e-6), rtol=1e-5)


def _route_before_the_epsilon(layer, params, tokens, route_bias):
    """``RoutedExperts.route`` as it stood before ``topk_eps``."""
    s = jax.nn.sigmoid(jnp.dot(
        tokens, params["router"].astype(tokens.dtype),
        preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(
        layer._in_kept_groups(jax.lax.stop_gradient(s) + route_bias),
        layer.top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if layer.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * layer.scaling


def test_no_epsilon_traces_the_routing_before_the_option():
    """``topk_eps`` 0 (the GLM and Ling cells) traces the routing it
    traced before the field."""
    layer = RoutedExperts(n_in=16, hidden_size=8, n_experts=16, top_k=4,
                          scaling=2.5)
    params = layer.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    bias = jnp.zeros((16,))

    def jaxpr(fn):
        return str(jax.make_jaxpr(lambda p, a: fn(p, a)[1].sum())(
            params, tokens))

    assert jaxpr(lambda p, a: layer.route(p, a, bias)) == jaxpr(
        lambda p, a: _route_before_the_epsilon(layer, p, a, bias))


def test_expert_shares_add_up_to_the_uncut_layer():
    """One chip's share: eight layers of 8 held experts each (as 8 chips
    hold LFM2's 64), routing over all 64 with no shared expert, add up to
    the uncut layer's output and input gradient, and to the reference's
    expert layer at the uncut share. Nothing is computed by every chip
    alike, so nothing is counted once."""
    ref, cfg = _ref(), _cfg()
    kw = dict(n_in=16, hidden_size=8, n_experts=64, top_k=4, n_shared=0,
              topk_eps=1e-6)
    whole = RoutedExperts(**kw)
    params = {k: v * 4 for k, v in whole.init_params(
        jax.random.PRNGKey(0)).items()}
    bias = jax.random.normal(jax.random.PRNGKey(5), (64,)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 16))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def run(layer, p):
        state = {**layer.init_state(), "route_bias": bias}
        out, vjp = jax.vjp(lambda a: layer.apply(p, a, state)[0], x)
        return out, vjp(probe)[0]

    full_out, full_dx = run(whole, params)
    total_out = total_dx = 0.0
    for first in range(0, 64, 8):
        share = RoutedExperts(held_first=first, held_last=first + 7, **kw)
        p = {"router": params["router"],
             **{k: params[k][first:first + 8] for k in ("Eg", "Eu", "Ed")}}
        out, dx = run(share, p)
        total_out, total_dx = total_out + out, total_dx + dx
    assert _close(total_out, full_out, 1e-5)
    assert _close(total_dx, full_dx, 1e-5)
    s = {**ref._sizes(cfg), "held": 64, "first": 0, "experts": 64, "k": 4}
    want = jax.vmap(lambda row: ref._experts(s, params, row, _mm, bias))(x)
    assert _close(full_out, want, 1e-5)


def _program_kwargs(cfg):
    from benchmarks.harness.spec import load_module

    driver = load_module("drivers", "fit_tokens")
    kwargs = {k: driver.lookup(cfg, path)
              for k, path in cfg["program"]["args"].items()}
    kwargs.update(cfg["program"]["kwargs"])
    kwargs.update(dtype="float32", compute_dtype=None)
    return kwargs


def test_stack_matches_the_reference_loss_and_gradients():
    """The zoo's stack at the configuration's tiny sizes: the step
    program's loss and every leaf's gradient against the reference, in
    float32 (1e-5 of the loss; 2e-4 of a leaf's largest gradient: the
    same float32 sums in another order through nine blocks); the layer
    pattern is the configuration's (attention at layers 2 and 6, a
    dense MLP at layer 1 only) and the head is the embedding's."""
    ref, cfg = _ref(), _cfg()
    net = MultiLayerNetwork(zoo.hybrid_conv_moe_lm(
        **_program_kwargs(cfg), updater="ADAM", learning_rate=1e-3, seed=3))
    kinds = [type(layer.attention).__name__ for layer in net.conf.layers[1:-1]]
    conv, att = "GatedShortConv", "GroupedQueryAttention"
    assert kinds == [conv, att, conv, conv, conv, att, conv, conv, conv]
    ffns = [type(layer.ffn).__name__ for layer in net.conf.layers[1:-1]]
    assert ffns == ["GatedFeedForward"] + ["RoutedExperts"] * 8
    assert net.conf.layers[-1].tied_params() == (("embed", 0, "W"),)
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


class _Losses:
    supports_batched_iterations = True

    def __init__(self):
        self.losses = []

    def iteration_done(self, model, iteration):
        self.losses.append(float(model.score_value))


def test_fit_follows_the_reference_losses():
    """``fit()`` of the tiny stack (the scan driver, full remat, Adam at
    the configuration's rate) over 16 batches against the reference's
    first 16 optimizer steps from the same weights on the same batches:
    every step's loss within 1e-5 of it (float32 on both sides; Adam's
    update from gradients that agree to 2e-4 moves a loss of 4 by far
    less)."""
    from benchmarks.harness import token_train

    ref, cfg = _ref(), _cfg()
    kwargs = {**_program_kwargs(cfg), "remat": "full"}
    net = MultiLayerNetwork(zoo.hybrid_conv_moe_lm(
        **kwargs, updater="ADAM",
        learning_rate=cfg["updater"]["learning_rate"], seed=3))
    key = jax.random.PRNGKey(11)
    net.init(params=jax.jit(lambda k: ref.init(cfg, k)[0])(key))
    batches = token_train.make_batches(cfg["input"], 2, 16, 11,
                                       cfg["vocab_size"], 1)
    listener = _Losses()
    net.set_listeners(listener)
    net.fit(ListDataSetIterator(batches))
    want = token_train.run_reference(
        ref, {**cfg, "updater": {k: v for k, v in cfg["updater"].items()
                                 if k != "schedule"}},
        key, batches, 16)["losses"]
    assert len(listener.losses) == 16
    np.testing.assert_allclose(listener.losses, want, rtol=1e-5)
