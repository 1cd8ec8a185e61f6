"""The latent-attention, routed-expert language model (``nn/layers/
decoder.py``, ``zoo.latent_moe_lm``) against the plain reference the
benchmark keeps for it, at the configuration's ``tiny`` sizes on the
CPU with seeded weights."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.nn import losses
from deeplearning4j_tpu.nn.layers import (
    LatentAttention,
    RoutedExperts,
    publish_routing_metrics,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIGHEST = jax.lax.Precision.HIGHEST


def _load(kind, name):
    from benchmarks.harness.spec import load_module

    return load_module(kind, name)


def _tiny(held):
    """The configuration's tiny sizes with experts ``held`` (first,
    last) of its 8."""
    with open(os.path.join(
            REPO, "benchmarks/configs/glm47flash_ep8share.json")) as f:
        cfg = json.load(f)
    cfg = {**{k: v for k, v in cfg.items() if k != "tiny"}, **cfg["tiny"]}
    cfg["deployment"] = dict(cfg["deployment"], held_experts=list(held))
    cfg["n_routed_experts"] = held[1] - held[0] + 1
    return cfg


def _net(cfg, **over):
    driver = _load("drivers", "fit_tokens")
    kwargs = {k: driver.lookup(cfg, path)
              for k, path in cfg["program"]["args"].items()}
    kwargs.update(cfg["program"]["kwargs"])
    kwargs.update(dtype="float32", compute_dtype=None, **over)
    return MultiLayerNetwork(zoo.latent_moe_lm(
        **kwargs, updater="ADAM", learning_rate=1e-3, seed=3))


def _ids(cfg, batch=4, seed=0):
    rng = np.random.default_rng(seed)
    t = cfg["input"]["length"]
    ids = rng.integers(0, cfg["vocab_size"], (batch, t + 2))
    return (jnp.asarray(ids[:, :t], jnp.float32),
            jnp.asarray(ids[:, 1:], jnp.float32))


def _close(a, b, tol):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-12)


@pytest.mark.parametrize("held", [(2, 3), (0, 7)], ids=["held2", "held8"])
def test_program_matches_reference_loss_gradients_and_adam_step(held):
    """Loss, every leaf's gradient and one Adam step of ``fit()``'s
    step program against the reference, with 2 of 8 experts held and
    with all 8."""
    from benchmarks.harness import reference_train

    cfg = _tiny(held)
    ref = _load("references", "glm47flash_ep8share")
    params, ref_state = ref.init(cfg, jax.random.PRNGKey(5))
    net = _net(cfg).init(params=jax.tree.map(jnp.copy, params))
    x, y = _ids(cfg)
    (want, ref_state), grads = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, ref_state, x, y), has_aux=True)(params)
    score = lambda p: net._score_pure(  # noqa: E731
        p, net.state, x, y, None, None, train=True)
    (got, state), got_grads = jax.value_and_grad(score, has_aux=True)(params)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    # the selection bias is state and no step moves it
    for layer, name in (("2", "route_bias"), ("3", "route_bias"),
                        ("4", "mtp_route_bias")):
        assert not np.asarray(state[layer][name]).any()
        assert not np.asarray(ref_state[layer][name]).any()
    for layer, leaves in grads.items():
        assert set(leaves) == set(got_grads[layer])
        for name, g in leaves.items():
            assert _close(got_grads[layer][name], g, 2e-4), (layer, name)
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
            assert np.abs(mine - moved).max() <= 0.02 * 1e-3, (layer, name)


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of 2 experts give, plus
    the shared expert once, are the uncut layer's output and input
    gradient."""
    whole = RoutedExperts(n_in=32, hidden_size=16, n_experts=8, top_k=2,
                          n_shared=1, scaling=1.8)
    params = whole.init_params(jax.random.PRNGKey(0))
    params = {k: v * 4 for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 32))
    probe = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 32))

    def run(layer, p):
        fn = lambda a: layer.apply(p, a, layer.init_state())[0]  # noqa: E731
        out, vjp = jax.vjp(fn, x)
        return out, vjp(probe)[0]

    full_out, full_dx = run(whole, params)
    no_shared = dict(n_in=32, hidden_size=16, n_experts=8, top_k=2,
                     n_shared=0, scaling=1.8)
    shared_only = {k: params[k] for k in ("Sg", "Su", "Sd")}
    tokens = x.reshape(-1, 32)
    f = lambda a: ((jax.nn.silu(a @ shared_only["Sg"])  # noqa: E731
                    * (a @ shared_only["Su"])) @ shared_only["Sd"])
    out, vjp = jax.vjp(f, tokens)
    total_out = out.reshape(x.shape)
    total_dx = vjp(probe.reshape(-1, 32))[0].reshape(x.shape)
    for first in (0, 2, 4, 6):
        share = RoutedExperts(held_first=first, held_last=first + 1,
                              **no_shared)
        p = {"router": params["router"],
             **{k: params[k][first:first + 2] for k in ("Eg", "Eu", "Ed")}}
        out, dx = run(share, p)
        total_out, total_dx = total_out + out, total_dx + dx
    assert _close(total_out, full_out, 1e-5)
    assert _close(total_dx, full_dx, 1e-5)


def _dense_combine(layer, params, x, route_bias):
    """The held experts' part by the one-hot combine: every held expert
    over every token, weighted by the routing."""
    tokens = x.reshape(-1, x.shape[-1])
    chosen, w = layer.route(params, tokens, route_bias)
    first, last = layer.held()
    want = jnp.zeros_like(tokens)
    for e in range(first, last + 1):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        y = (jax.nn.silu(tokens @ params["Eg"][e - first])
             * (tokens @ params["Eu"][e - first])) @ params["Ed"][e - first]
        want = want + mine[:, None] * y
    return want.reshape(x.shape)


def test_no_token_dropped_under_a_skewed_router():
    """A router biased so that one held expert takes every token (half
    of all slots, nine tenths of the held ones): every slot of a held
    expert is computed (the dense one-hot combine agrees) and the
    counters say so."""
    layer = RoutedExperts(n_in=16, hidden_size=8, n_experts=8,
                          held_first=2, held_last=3, top_k=2, n_shared=0)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
    state = layer.init_state()
    state["route_bias"] = state["route_bias"].at[2].set(10.0)
    out, new = layer.apply(params, x, state)
    slots = np.asarray(new["slots"])
    assert slots.sum() == 2 * 64 * 2
    assert slots[2] == 128 > 0.75 * slots[2:4].sum()
    assert int(new["dropped"]) == 0
    assert _close(
        out, _dense_combine(layer, params, x, state["route_bias"]), 1e-5)


@pytest.mark.parametrize("n_experts, held, bias, rung", [
    (8, (2, 3), 0.0, 0), (8, (2, 3), 10.0, 1),
    (16, (2, 3), 0.0, 0), (16, (2, 3), 0.5, 1), (16, (2, 3), 10.0, 2),
    (8, (0, 7), 0.0, 0),
], ids=["held2of8-even-first", "held2of8-skewed-last",
        "held2of16-even-first", "held2of16-nudged-middle",
        "held2of16-skewed-last", "held8-one-rung"])
def test_every_rung_of_the_row_ladder_is_the_dense_combine(
        n_experts, held, bias, rung):
    """The held experts' data path runs over the first rung of static
    row bounds that holds the rows they were sent: near-even routing
    takes the first, a selection bias on one held expert (``bias``) a
    later one; at each the output, the input gradient and the three
    expert stacks' gradients are the dense one-hot combine's, no slot
    is dropped and ``rung_calls`` counts the call at that rung. With
    every expert held the ladder has one rung and no conditional."""
    layer = RoutedExperts(n_in=16, hidden_size=8, n_experts=n_experts,
                          held_first=held[0], held_last=held[1], top_k=2,
                          n_shared=0, scaling=1.8)
    params = {k: v * 4 for k, v in
              layer.init_params(jax.random.PRNGKey(0)).items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 16))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    state = layer.init_state()
    state["route_bias"] = state["route_bias"].at[2].set(bias)
    rungs = layer.rungs(2 * 128 * 2)
    assert rungs == {(8, 2): (256, 512), (16, 2): (128, 256, 512),
                     (8, 0): (512,)}[n_experts, held[0]]
    assert state["rung_calls"].shape == (len(rungs),)

    def mine(p, a):
        y, new = layer.apply(p, a, state)
        return jnp.sum(y * probe), new

    def dense(p, a):
        return jnp.sum(
            _dense_combine(layer, p, a, state["route_bias"]) * probe)

    (got, new), grads = jax.value_and_grad(
        mine, (0, 1), has_aux=True)(params, x)
    want, want_grads = jax.value_and_grad(dense, (0, 1))(params, x)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert _close(grads[1], want_grads[1], 1e-5)
    for name in ("Eg", "Eu", "Ed", "router"):
        assert _close(grads[0][name], want_grads[0][name], 1e-5), name
    held_slots = int(np.asarray(new["slots"])[held[0]:held[1] + 1].sum())
    assert held_slots <= rungs[rung] and (
        rung == 0 or held_slots > rungs[rung - 1])
    assert int(new["dropped"]) == 0
    assert np.asarray(new["rung_calls"]).tolist() == [
        int(i == rung) for i in range(len(rungs))]
    text = str(jax.make_jaxpr(lambda p, a: layer.apply(p, a, state))(
        params, x))
    assert ("cond[" in text) == (len(rungs) > 1)


def _softmax_attention(layer, p, x):
    """Latent attention with a plain masked softmax, float32."""
    from deeplearning4j_tpu.nn.layers.decoder import rms_norm, rotary

    b, t, _ = x.shape
    h, nope, rope, vd = (layer.n_heads, layer.nope_dim, layer.rope_dim,
                         layer.v_dim)
    mm = lambda a, w: jnp.matmul(a, w, precision=HIGHEST)  # noqa: E731
    q = rotary(mm(rms_norm(mm(x, p["Wqa"]), p["q_norm"], layer.eps),
                  p["Wqb"]).reshape(b, t, h, nope + rope),
               layer.rope_theta, rope)
    ckv = mm(x, p["Wkva"])
    k_pe = rotary(ckv[..., None, layer.kv_rank:], layer.rope_theta, rope)
    kv = mm(rms_norm(ckv[..., :layer.kv_rank], p["kv_norm"], layer.eps),
            p["Wkvb"]).reshape(b, t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, rope))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(
        nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:],
                   precision=HIGHEST)
    return mm(o.reshape(b, t, h * vd), p["Wo"])


def test_latent_attention_takes_the_flash_pair_at_head_size_256(
        monkeypatch):
    """Latent attention alone against a masked-softmax reference (its
    output, and the gradients of the query-side and key/value-side
    weights), the flash pair forced through the interpreter at head
    size 256 and counted."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    dispatch.reset_for_tests()
    try:
        layer = LatentAttention(
            n_in=64, n_heads=2, q_rank=48, kv_rank=32, nope_dim=192,
            rope_dim=64, v_dim=256, rope_theta=1e6)
        p = {k: v * (8.0 if k.startswith("W") else 1.0)
             for k, v in layer.init_params(jax.random.PRNGKey(0)).items()}
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 64))
        probe = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 64))

        def before():
            fam = default_registry().get("pallas_dispatch_total")
            return {c.label_values: c.value
                    for c in (fam.children() if fam else [])}

        c0 = before()
        got, got_g = jax.value_and_grad(
            lambda p_: jnp.sum(layer.apply(p_, x, {})[0] * probe))(p)
        c1 = before()
        want, want_g = jax.value_and_grad(
            lambda p_: jnp.sum(_softmax_attention(layer, p_, x) * probe))(p)
    finally:
        monkeypatch.delenv("DL4J_TPU_PALLAS")
        dispatch.reset_for_tests()
    added = {k: c1[k] - c0.get(k, 0) for k in c1 if c1[k] != c0.get(k, 0)}
    assert added == {("flash_attention", "interpret"): 1,
                     ("flash_attention_bwd", "interpret"): 1}
    assert abs(float(got) - float(want)) < 2e-3 * abs(float(want))
    for name in ("Wqa", "Wqb", "Wkva", "Wkvb", "Wo", "q_norm", "kv_norm"):
        assert _close(got_g[name], want_g[name], 5e-3), name


def test_integer_label_loss_is_mcxent_of_the_one_hot():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(12, 7)), jnp.float32)
    ids = rng.integers(0, 7, 12)
    want = losses.score("MCXENT", jnp.eye(7)[ids], logits, "softmax")
    got = losses.score("SPARSE_MCXENT", jnp.asarray(ids, jnp.float32)[:, None],
                       logits, "softmax")
    assert abs(float(got) - float(want)) < 1e-6
    h = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 7)), jnp.float32)
    whole = lambda h_, w_: losses.score(  # noqa: E731
        "MCXENT", jnp.eye(7)[ids], h_ @ w_, "softmax") * 12
    for block in (0, 4):
        blocked = lambda h_, w_: losses.sparse_mcxent_sum(  # noqa: E731
            h_, w_, jnp.asarray(ids), block)
        assert abs(float(blocked(h, w)) - float(whole(h, w))) < 1e-4
        for a, b in zip(jax.grad(blocked, (0, 1))(h, w),
                        jax.grad(whole, (0, 1))(h, w)):
            assert _close(a, b, 1e-5)


def test_expert_blocks_publish_their_routing_through_the_scan_program():
    """The expert blocks carry state, so they are no scan-over-layers
    run; through ``fit()``'s scan program their routing statistics
    reach the metrics registry, and publishing starts them anew."""
    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator

    cfg = _tiny((2, 3))
    ref = _load("references", "glm47flash_ep8share")
    params, _ = ref.init(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(1)
    t = cfg["input"]["length"]
    batches = []
    for _ in range(16):
        ids = rng.integers(0, cfg["vocab_size"], (2, t + 2)).astype(np.uint16)
        batches.append(DataSet(features=ids[:, :t], labels=ids[:, 1:]))
    net = _net(cfg, scan_layers=True, remat="full").init(
        params=jax.tree.map(jnp.copy, params))
    assert net._active_layer_runs() == ()
    net.fit(ListDataSetIterator(batches), epochs=1)
    assert net._jit_multi_step is not None and net._jit_step is None
    fam = default_registry().get("moe_token_slots_total")
    before = ({c.label_values: c.value for c in fam.children()}
              if fam else {})
    report = publish_routing_metrics(net)
    assert set(report) == {"2", "3", "4"}
    per_layer = 16 * 2 * t * cfg["num_experts_per_tok"]
    assert all(sum(r["slots"]) == per_layer and r["dropped"] == 0
               for r in report.values())
    # every call ran at a rung of the layer's ladder
    a_call = per_layer // 16
    rungs = net.conf.layers[2].ffn.rungs(a_call)
    assert rungs[-1] == a_call and len(rungs) == 2
    assert all(16 * rungs[0] <= r["rows_covered"] <= per_layer
               for r in report.values())
    rung_calls = default_registry().get("moe_rung_calls_total")
    assert {c.label_values[1] for c in rung_calls.children()
            if c.label_values[0] == "2"} == {str(r) for r in rungs}
    fam = default_registry().get("moe_token_slots_total")
    held = {c.label_values: c.value for c in fam.children()}
    assert (held[("2", "true")] - before.get(("2", "true"), 0)
            == sum(report["2"]["slots"][2:4]))
    assert default_registry().get("moe_dropped_tokens_total").value == 0
    # the state's counts start anew, the selection bias stays
    assert not np.asarray(net.state["2"]["slots"]).any()
    assert not np.asarray(net.state["4"]["mtp_slots"]).any()
    assert set(net.state["4"]) == {"mtp_route_bias", "mtp_slots",
                                   "mtp_dropped", "mtp_rung_calls"}
    assert publish_routing_metrics(net) == report
    again = {c.label_values: c.value for c in fam.children()}
    assert again == held
