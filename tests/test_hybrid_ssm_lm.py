"""The hybrid state-space language model (``nn/layers/state_space.py``,
``GroupedQueryAttention`` and the scaled-residual block of ``nn/layers/
decoder.py``, ``zoo.hybrid_ssm_lm``) against the plain reference the
benchmark keeps for it, at the configuration's ``tiny`` sizes on the
CPU with seeded weights."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.nn.layers import (
    GroupedQueryAttention,
    StateSpaceMixer,
)
from deeplearning4j_tpu.nn.layers.state_space import (
    causal_depthwise_conv,
    ssd_chunked,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import default_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(
    REPO, "benchmarks/configs/granite40hmicro_stage10.json")
MULTIPLIERS = ("attention_multiplier", "residual_multiplier",
               "embedding_multiplier", "logits_scaling")


def _load(kind, name="granite40hmicro_stage10"):
    from benchmarks.harness.spec import load_module

    return load_module(kind, name)


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def _tiny():
    cfg = _published()
    return {**{k: v for k, v in cfg.items() if k != "tiny"}, **cfg["tiny"]}


def _net(cfg, compute_dtype=None, **over):
    driver = _load("drivers", "fit_tokens")
    kwargs = {k: driver.lookup(cfg, path)
              for k, path in cfg["program"]["args"].items()}
    kwargs.update(cfg["program"]["kwargs"])
    kwargs.update(dtype="float32", compute_dtype=compute_dtype, **over)
    return MultiLayerNetwork(zoo.hybrid_ssm_lm(
        **kwargs, updater="ADAM", learning_rate=1e-3, seed=3))


def _ids(cfg, batch=4, seed=0):
    rng = np.random.default_rng(seed)
    t = cfg["input"]["length"]
    ids = rng.integers(0, cfg["vocab_size"], (batch, t + 1))
    return (jnp.asarray(ids[:, :t], jnp.float32),
            jnp.asarray(ids[:, 1:], jnp.float32))


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-12)


def _program_score(net, x, y):
    return lambda p: net._score_pure(  # noqa: E731
        p, net.state, x, y, None, None, train=True)


# float32: the two differ by the order of float32 sums alone (the
# chunked scan against the full decay-masked product; read: 5e-6 at the
# worst leaf); bfloat16: every product's operands and every weight are
# rounded to 8 bits, 0.4% each (read: 1-2% on most leaves, 6% on an
# ``A_log`` whose gradient is 1e-8)
@pytest.mark.parametrize("compute, loss_tol, grad_tol, step_tol", [
    (None, 1e-5, 5e-5, 1e-3), ("bfloat16", 1e-3, 0.15, 0.3)],
    ids=["float32", "bfloat16"])
def test_program_matches_reference_loss_gradients_and_adam_step(
        compute, loss_tol, grad_tol, step_tol):
    """Loss, every leaf's gradient and one Adam step of ``fit()``'s
    step program against the reference."""
    from benchmarks.harness import reference_train

    cfg = _tiny()
    ref = _load("references")
    params, ref_state = ref.init(cfg, jax.random.PRNGKey(5))
    net = _net(cfg, compute).init(params=jax.tree.map(jnp.copy, params))
    x, y = _ids(cfg)
    (want, _), grads = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, ref_state, x, y), has_aux=True)(params)
    (got, _), got_grads = jax.value_and_grad(
        _program_score(net, x, y), has_aux=True)(params)
    assert abs(float(got) - float(want)) < loss_tol * float(want)
    assert set(grads) == set(got_grads) == set(net.params)
    for layer, leaves in grads.items():
        assert set(leaves) == set(got_grads[layer])
        for name, g in leaves.items():
            assert _close(got_grads[layer][name], g, grad_tol), (layer, name)
    upd = {"name": "ADAM", "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
    zeros = jax.tree.map(jnp.zeros_like, params)
    stepped, _ = reference_train.apply_updater(
        upd, params, grads, (zeros, zeros), 1.0, 1e-3)
    net.fit(np.asarray(x), np.asarray(y), epochs=1)
    for layer, leaves in stepped.items():
        for name, w in leaves.items():
            moved = np.asarray(w) - np.asarray(params[layer][name])
            mine = (np.asarray(net.params[layer][name])
                    - np.asarray(params[layer][name]))
            # Adam's first step is the rate times the gradient's sign
            # wherever |g| is far above epsilon: in bfloat16 the
            # smallest gradients of a leaf change sign (read: 0.16 of
            # the step's norm at the worst leaf, 2e-4 in float32)
            assert (np.linalg.norm(mine - moved)
                    <= step_tol * np.linalg.norm(moved)), (layer, name)


def _scan_inputs(t=64, h=4, p=8, g=2, n=8, seed=0):
    """Inputs of the scan with decays from both ends of the assumed
    range: Δ log-uniform on [0.001, 0.1] around each head's own, A from
    -1 to -16."""
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    dt0 = np.geomspace(1e-3, 1e-1, h)
    return (f32(rng.normal(size=(2, t, h, p))),
            f32(dt0 * np.exp(0.3 * rng.normal(size=(2, t, h)))),
            f32(-np.linspace(1.0, 16.0, h)),
            f32(rng.normal(size=(2, t, g, n))),
            f32(rng.normal(size=(2, t, g, n))))


def _recurrence(x, dt, a, b_in, c_in):
    """``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ``, ``y_t = S_t C_t``,
    one position at a time."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    b_h = jnp.repeat(b_in, h // g, axis=2)
    c_h = jnp.repeat(c_in, h // g, axis=2)

    def step(s, per):
        x_t, dt_t, b_t, c_t = per
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b_h, c_h)))
    return jnp.moveaxis(y, 0, 1)


def test_chunked_scan_is_the_recurrence_in_output_and_gradients():
    """Eight chunks of 8 positions, two groups, every head's memory
    from a few positions to the whole sequence."""
    args = _scan_inputs()
    want = _recurrence(*args)
    got = ssd_chunked(*args, chunk=8)
    assert got.dtype == jnp.float32 and _close(got, want, 2e-5)
    # the last position of a slow head still holds the first chunk
    assert float(jnp.exp(jnp.sum(args[1][0, :, 0]) * args[2][0])) > 0.5
    weight = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                         jnp.float32)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * weight), range(5))(*args)
             for f in (lambda *a: ssd_chunked(*a, chunk=8), _recurrence)]
    for mine, theirs, name in zip(*grads, ("x", "dt", "a", "B", "C")):
        assert _close(mine, theirs, 1e-4), name


@pytest.mark.parametrize("chunk", [16, 24, 64, 256])
def test_the_chunk_size_does_not_change_the_scan(chunk):
    """Another chunk, one that does not divide the length (padded
    behind) and one chunk for everything give the result of chunk 8."""
    args = _scan_inputs(seed=2)
    assert _close(ssd_chunked(*args, chunk=chunk),
                  ssd_chunked(*args, chunk=8), 2e-5)


def test_causal_depthwise_convolution_is_xlas_grouped_one():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    want = jax.lax.conv_general_dilated(
        x, w[:, None, :], (1,), [(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=6) + bias
    assert _close(causal_depthwise_conv(x, w, bias), want, 1e-6)


def test_a_tokens_output_is_unchanged_by_any_later_token():
    """Convolution, scan and attention are causal: the probabilities
    at positions 0..k do not move when ids behind k change, and those
    behind k do."""
    cfg = _tiny()
    net = _net(cfg).init()
    x, _ = _ids(cfg, batch=2)
    k = 13
    other = x.at[:, k + 1:].set((x[:, k + 1:] + 7) % cfg["vocab_size"])
    a, b = np.asarray(net.output(x)), np.asarray(net.output(other))
    assert a.shape == (2, cfg["input"]["length"], cfg["vocab_size"])
    assert np.array_equal(a[:, :k + 1], b[:, :k + 1])
    assert np.abs(a[:, k + 1:] - b[:, k + 1:]).max() > 1e-4


def test_grouped_query_attention_is_the_reference_with_heads_repeated():
    cfg = _tiny()
    ref = _load("references")
    s = ref._sizes(cfg)
    layer = GroupedQueryAttention(
        n_in=s["d"], n_heads=s["qh"], n_kv_heads=s["kvh"],
        head_dim=s["hd"], scale=cfg["attention_multiplier"])
    rng = np.random.default_rng(3)
    p = {k: jnp.asarray(rng.normal(size=v.shape) * 0.2, jnp.float32)
         for k, v in layer.init_params(jax.random.PRNGKey(0)).items()}
    assert p["Wk"].shape == (s["d"], s["kvh"] * s["hd"])
    u = jnp.asarray(rng.normal(size=(3, 32, s["d"])), jnp.float32)
    got, _ = layer.apply(p, u, {})
    mm = lambda a, w: jnp.matmul(  # noqa: E731
        a, w, precision=jax.lax.Precision.HIGHEST)
    want = jnp.stack([ref._attention(s, p, row, mm, ref._exact)
                      for row in u])
    assert _close(got, want, 1e-5)
    # a key/value head serves its own four... here two query heads:
    # moving key/value head 1 moves query heads 2 and 3 alone
    hd = s["hd"]
    moved = dict(p, Wv=p["Wv"].at[:, hd:].add(1.0))
    pre = lambda q: layer.apply(  # noqa: E731
        dict(q, Wo=jnp.eye(s["qh"] * hd)), u, {})[0]
    diff = np.abs(np.asarray(pre(moved) - pre(p))).max(axis=(0, 1))
    assert not diff[:2 * hd].any() and diff[2 * hd:].all()


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_acts(name):
    """With the configuration's constants the program's loss is the
    reference's (read: 1e-7 apart); with one of them doubled it is not
    (1e-4 for the attention block's, 2e-3 to 8e-3 for the others).
    Matrices of std 0.3, so that scores and branches carry weight."""
    cfg = dict(_tiny(), init={"std": 0.3})
    ref = _load("references")
    params, state = ref.init(cfg, jax.random.PRNGKey(9))
    x, y = _ids(cfg, seed=4)
    want = float(ref.loss(cfg, params, state, x, y)[0])

    def program(**over):
        net = _net(cfg, **over).init(params=jax.tree.map(jnp.copy, params))
        return float(_program_score(net, x, y)(params)[0])

    assert abs(program() - want) < 2e-6 * want
    assert abs(program(**{name: 2 * cfg[name]}) - want) > 5e-5 * want


def test_tied_heads_gradient_reaches_the_embedding_from_both_uses():
    """The embedding's gradient under the tied head is the untied
    model's embedding gradient plus its head's, transposed; rows no
    input id names get the head's part alone."""
    cfg = _tiny()
    params, _ = _load("references").init(cfg, jax.random.PRNGKey(2))
    x, y = _ids(cfg, seed=5)
    tied = _net(cfg).init(params=jax.tree.map(jnp.copy, params))
    assert set(tied.params["6"]) == {"norm"}
    assert tied.num_params() == sum(
        int(v.size) for leaves in params.values() for v in leaves.values())
    g_tied = jax.grad(lambda p: _program_score(tied, x, y)(p)[0])(params)
    split = {**params, "6": {**params["6"], "W": params["0"]["W"].T}}
    untied = _net(cfg, tie_embeddings=False).init(
        params=jax.tree.map(jnp.copy, split))
    g = jax.grad(lambda p: _program_score(untied, x, y)(p)[0])(split)
    assert _close(g_tied["0"]["W"], g["0"]["W"] + g["6"]["W"].T, 1e-5)
    unseen = np.setdiff1d(np.arange(cfg["vocab_size"]),
                          np.asarray(x, np.int64))
    assert unseen.size and not np.asarray(g["0"]["W"])[unseen].any()
    assert np.abs(np.asarray(g_tied["0"]["W"])[unseen]).min() > 0


def test_the_configurations_layers_are_the_first_published_period():
    cfg = _published()
    published = cfg["published"]
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"] == period == published["layer_types"][:10]
    assert published["layer_types"] == period * 4
    assert [i for i, k in enumerate(published["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 10
    assert published["num_hidden_layers"] == 40
    assert cfg["vocab_size"] * 8 == published["vocab_size"] == 100352
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "vocab_size"}
    # every width as published
    assert (cfg["hidden_size"], cfg["shared_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"]) == (2048, 8192, 32, 8, 64, 64, 128,
                                         1, 4, 256)
    assert _load("counts").parameters(cfg) == 772_160_448


def test_fit_trains_on_ids_through_the_scan_path_with_two_layer_runs():
    """``fit()`` on uint16 ids and labels: one scan-of-16 program, the
    two runs of Mamba blocks scanned around the attention block, the
    state-space layer traced once a run; the result is the unrolled
    stack's."""
    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator

    cfg = _tiny()
    params, _ = _load("references").init(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(1)
    t = cfg["input"]["length"]
    batches = []
    for _ in range(16):
        ids = rng.integers(0, cfg["vocab_size"], (2, t + 1)).astype(np.uint16)
        batches.append(DataSet(features=ids[:, :t], labels=ids[:, 1:]))

    def calls():
        fam = default_registry().get("ssm_scan_calls_total")
        return {c.label_values: c.value for c in fam.children()} if fam \
            else {}

    before = calls().get(("8", "4"), 0)
    net = _net(cfg, scan_layers=True).init(
        params=jax.tree.map(jnp.copy, params))
    assert net.scan_layers and net.remat == "full"
    assert net._active_layer_runs() == ((1, 3), (4, 6))
    net.fit(ListDataSetIterator(batches), epochs=1)
    assert net._jit_multi_step is not None and net._jit_step is None
    assert net.iteration_count == 16 and np.isfinite(net.score_value)
    # chunk 8 over 32 positions; a scanned run traces its layer once
    # (forward; remat's recomputation and the scan's own tracing may
    # trace it again, never once a layer)
    traced = calls()[("8", "4")] - before
    assert 2 <= traced
    # the configuration's own choice (its ``assumed`` says why)
    assert cfg["program"]["kwargs"]["scan_layers"] is False
    unrolled = _net(cfg).init(params=jax.tree.map(jnp.copy, params))
    assert not unrolled.scan_layers
    unrolled.fit(ListDataSetIterator(batches), epochs=1)
    for layer, leaves in net.params.items():
        for name, w in leaves.items():
            assert _close(w, unrolled.params[layer][name], 2e-3), (
                layer, name)


def test_the_compiled_step_carries_every_parts_scope():
    cfg = _tiny()
    net = _net(cfg).init()
    x, y = _ids(cfg, batch=2)
    text = jax.jit(jax.grad(
        lambda p: _program_score(net, x, y)(p)[0])).lower(
            net.params).compile().as_text()
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan/ssm.scan.intra",
                  "ssm.scan/ssm.scan.states", "ssm.scan/ssm.scan.pass",
                  "ssm.scan/ssm.scan.inter", "ssm.gate_norm",
                  "ssm.out_proj", "gqa.qkv", "gqa.attention", "gqa.out",
                  "mlp", "lm_head"):
        assert scope in text, scope


def test_a_fresh_mixer_starts_in_the_assumed_ranges():
    layer = StateSpaceMixer(n_in=32, n_heads=16, head_dim=8, state_size=8,
                            weight_init="DISTRIBUTION")
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["Win"].shape == (32, 2 * 128 + 2 * 8 + 16)
    assert p["conv_W"].shape == (4, 128 + 16)
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1e-3 <= step.min() and step.max() <= 1e-1
    a = np.exp(np.asarray(p["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert np.abs(np.asarray(p["conv_W"])).max() <= 0.5
    assert not np.asarray(p["conv_b"]).any()
    assert np.array_equal(np.asarray(p["D"]), np.ones(16))
