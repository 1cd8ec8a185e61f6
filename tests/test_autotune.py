"""Autotuned kernel library (``ops/autotune.py`` + ``ops/tiling.py``).

Contract under test: block-config resolution is a pure drop-in around
the divisor heuristics — ``DL4J_TPU_TUNE=off`` is byte-identical to
the pre-autotuner behavior, ``cached`` (the default) NEVER measures
and degrades to the heuristic on any miss, ``on`` measures misses and
persists winners under the ``compile/aot.py`` fingerprint discipline
(a stale/corrupt/infeasible entry is refused and counted, never
dispatched). The env knobs follow the read-once-per-process rule and
are re-read only through ``dispatch.reset_for_tests()`` — which the
autouse conftest fixture calls around every test, so each test here
starts with a cold tuner.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import kernel_tols, pallas_interpret
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    ConvolutionLayer,
    DenseLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import autotune, dispatch, tiling
from deeplearning4j_tpu.ops.matmul_block import matmul_block

CHAOS_SEED = int(os.environ.get("DL4J_TPU_CHAOS_SEED", "1337"))

# a synthetic resolution subject: resolve() is generic over (kernel,
# identity, candidate set), so the cache/fallback machinery is
# testable without timing real Pallas kernels
CANDS = [(2, 2), (4, 4), (8, 8)]
HEUR = (4, 4)
IDENT = {"m": 8, "n": 8, "dtype": "float32"}
KERNEL = "matmul_block"


def _counter(name, **labels):
    fam = default_registry().get(name)
    if fam is None:
        return 0.0
    if labels:
        return fam.labels(**labels).value
    return float(sum(c.value for c in fam.children()))


def _measure_count():
    fam = default_registry().get("tuner_measure_ms")
    if fam is None:
        return 0
    return int(sum(c.count for c in fam.children()))


def _factory_counting(calls):
    def factory(cfg):
        def run():
            calls.append(tuple(cfg))
        return run
    return factory


def _resolve(factory=None):
    return autotune.resolve(KERNEL, IDENT, HEUR, CANDS,
                            measure_factory=factory)


def _arm(monkeypatch, mode, cache_dir=None, budget_ms=None):
    monkeypatch.setenv("DL4J_TPU_TUNE", mode)
    if cache_dir is not None:
        monkeypatch.setenv("DL4J_TPU_TUNE_CACHE_DIR", str(cache_dir))
    else:
        monkeypatch.delenv("DL4J_TPU_TUNE_CACHE_DIR", raising=False)
    if budget_ms is not None:
        monkeypatch.setenv("DL4J_TPU_TUNE_BUDGET_MS", str(budget_ms))
    dispatch.reset_for_tests()


# ---------------------------------------------------------------------------
# env knob semantics
# ---------------------------------------------------------------------------


class TestModeSemantics:
    def test_default_mode_is_cached(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_TUNE", raising=False)
        dispatch.reset_for_tests()
        assert autotune.tuning_mode() == "cached"
        assert autotune.tuning_active()

    def test_off_mode_is_inactive(self, monkeypatch):
        _arm(monkeypatch, "off")
        assert autotune.tuning_mode() == "off"
        assert not autotune.tuning_active()

    def test_unknown_mode_falls_back_to_cached(self, monkeypatch):
        _arm(monkeypatch, "bogus")
        assert autotune.tuning_mode() == "cached"

    def test_reset_for_tests_rereads_env(self, monkeypatch):
        """The read-once regression: flipping the env mid-process does
        NOTHING until dispatch.reset_for_tests() cascades into the
        tuner (the autouse fixture relies on exactly this)."""
        _arm(monkeypatch, "off")
        assert autotune.tuning_mode() == "off"
        monkeypatch.setenv("DL4J_TPU_TUNE", "on")
        assert autotune.tuning_mode() == "off"  # cached read sticks
        dispatch.reset_for_tests()  # the cascade under test
        assert autotune.tuning_mode() == "on"

    def test_budget_and_cache_dir_knobs(self, monkeypatch, tmp_path):
        _arm(monkeypatch, "on", cache_dir=tmp_path, budget_ms="123.5")
        assert autotune.cache_dir() == str(tmp_path)
        assert autotune.measure_budget_ms() == 123.5

    def test_bad_budget_falls_back_to_default(self, monkeypatch):
        _arm(monkeypatch, "on", budget_ms="not-a-number")
        assert autotune.measure_budget_ms() == 2000.0


# ---------------------------------------------------------------------------
# resolution: off / cached / on
# ---------------------------------------------------------------------------


class TestResolution:
    def test_off_mode_returns_heuristic_untouched(self, monkeypatch):
        _arm(monkeypatch, "off")
        calls = []
        assert _resolve(_factory_counting(calls)) == HEUR
        assert calls == []

    def test_none_heuristic_propagates(self, monkeypatch, tmp_path):
        """Infeasible stays infeasible: tuning never changes routing."""
        _arm(monkeypatch, "on", cache_dir=tmp_path)
        got = autotune.resolve(KERNEL, IDENT, None, CANDS,
                               measure_factory=_factory_counting([]))
        assert got is None

    def test_cached_miss_falls_back_and_counts(self, monkeypatch,
                                               tmp_path):
        _arm(monkeypatch, "cached", cache_dir=tmp_path)
        before = _counter("tuner_fallback_total", kernel=KERNEL,
                          reason="absent")
        assert _resolve() == HEUR
        assert _counter("tuner_fallback_total", kernel=KERNEL,
                        reason="absent") == before + 1

    def test_cached_mode_never_measures(self, monkeypatch, tmp_path):
        """Even handed a measure factory, cached mode must not call
        it — zero-budget is the mode's contract, not the caller's."""
        _arm(monkeypatch, "cached", cache_dir=tmp_path)
        calls = []
        m0 = _measure_count()
        s0 = _counter("tuner_searches_total")
        assert _resolve(_factory_counting(calls)) == HEUR
        assert calls == []
        assert _measure_count() == m0
        assert _counter("tuner_searches_total") == s0

    def test_on_mode_searches_persists_and_rehits(self, monkeypatch,
                                                  tmp_path):
        _arm(monkeypatch, "on", cache_dir=tmp_path)
        calls = []
        s0 = _counter("tuner_searches_total", kernel=KERNEL)
        got = _resolve(_factory_counting(calls))
        assert got in [tuple(c) for c in CANDS]
        assert _counter("tuner_searches_total",
                        kernel=KERNEL) == s0 + 1
        assert calls  # measurement actually ran
        # heuristic is always among the measured configs
        assert HEUR in set(calls)

        path = autotune.entry_path(KERNEL, IDENT)
        assert path is not None and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["kernel"] == KERNEL
        assert doc["fingerprint"] == autotune.fingerprint(KERNEL)
        assert tuple(doc["config"]) in {tuple(c) for c in CANDS}
        assert autotune._cfg_tag(HEUR) in doc["timings_ms"]

        # warm re-resolve (fresh memo, cached mode): disk hit, no
        # factory call, same winner
        _arm(monkeypatch, "cached", cache_dir=tmp_path)
        h0 = _counter("tuner_cache_hits_total", kernel=KERNEL)
        calls2 = []
        assert _resolve(_factory_counting(calls2)) == got
        assert calls2 == []
        assert _counter("tuner_cache_hits_total",
                        kernel=KERNEL) == h0 + 1

    def test_resolution_is_memoized_per_process(self, monkeypatch,
                                                tmp_path):
        _arm(monkeypatch, "on", cache_dir=tmp_path)
        got = _resolve(_factory_counting([]))
        # mangle the entry on disk: the in-process memo must keep
        # serving the resolved config without re-reading the file
        path = autotune.entry_path(KERNEL, IDENT)
        with open(path, "w") as f:
            f.write("{mangled")
        s0 = _counter("tuner_searches_total")
        assert _resolve(_factory_counting([])) == got
        assert _counter("tuner_searches_total") == s0

    def test_no_cache_dir_on_mode_still_tunes(self, monkeypatch):
        """Without DL4J_TPU_TUNE_CACHE_DIR the search still runs and
        the winner is used — it just can't persist."""
        _arm(monkeypatch, "on")
        assert autotune.entry_path(KERNEL, IDENT) is None
        got = _resolve(_factory_counting([]))
        assert got in [tuple(c) for c in CANDS]


# ---------------------------------------------------------------------------
# cache integrity: refused, counted, never dispatched
# ---------------------------------------------------------------------------


def _write_valid_entry(config=HEUR):
    path = autotune.entry_path(KERNEL, IDENT)
    autotune._persist(path, {
        "format": 1,
        "fingerprint": autotune.fingerprint(KERNEL),
        "kernel": KERNEL,
        "identity": IDENT,
        "config": list(config),
        "best_ms": 1.0,
        "measured": 1,
        "timings_ms": {autotune._cfg_tag(config): 1.0},
    })
    return path


class TestCacheIntegrity:
    @staticmethod
    def _truncate(p):
        raw = open(p).read()
        with open(p, "w") as f:
            f.write(raw[:20])

    @pytest.mark.parametrize("mangle,reason", [
        (lambda p: open(p, "w").write("{nope"), "corrupt"),
        ("truncate", "corrupt"),
        (lambda p: open(p, "w").write("[1, 2]"), "corrupt"),
        (None, "stale"),                          # fingerprint flip
        (None, "invalid"),                        # infeasible config
    ])
    def test_mangled_entry_falls_back(self, monkeypatch, tmp_path,
                                      mangle, reason):
        _arm(monkeypatch, "cached", cache_dir=tmp_path)
        path = _write_valid_entry(config=(8, 8))
        if reason == "stale":
            with open(path) as f:
                doc = json.load(f)
            doc["fingerprint"] = "0" * 32
            with open(path, "w") as f:
                json.dump(doc, f)
        elif reason == "invalid":
            with open(path) as f:
                doc = json.load(f)
            doc["config"] = [3, 5]  # not in the candidate set
            with open(path, "w") as f:
                json.dump(doc, f)
        elif mangle == "truncate":
            self._truncate(path)
        else:
            mangle(path)
        before = _counter("tuner_fallback_total", kernel=KERNEL,
                          reason=reason)
        assert _resolve() == HEUR
        assert _counter("tuner_fallback_total", kernel=KERNEL,
                        reason=reason) == before + 1

    def test_valid_entry_hits(self, monkeypatch, tmp_path):
        _arm(monkeypatch, "cached", cache_dir=tmp_path)
        _write_valid_entry(config=(8, 8))
        h0 = _counter("tuner_cache_hits_total", kernel=KERNEL)
        assert _resolve() == (8, 8)
        assert _counter("tuner_cache_hits_total",
                        kernel=KERNEL) == h0 + 1

    def test_on_mode_refused_entry_remeasures_and_overwrites(
            self, monkeypatch, tmp_path):
        _arm(monkeypatch, "on", cache_dir=tmp_path)
        path = _write_valid_entry(config=(8, 8))
        with open(path) as f:
            doc = json.load(f)
        doc["fingerprint"] = "0" * 32
        with open(path, "w") as f:
            json.dump(doc, f)
        f0 = _counter("tuner_fallback_total", kernel=KERNEL,
                      reason="stale")
        s0 = _counter("tuner_searches_total", kernel=KERNEL)
        got = _resolve(_factory_counting([]))
        assert got in [tuple(c) for c in CANDS]
        assert _counter("tuner_fallback_total", kernel=KERNEL,
                        reason="stale") == f0 + 1
        assert _counter("tuner_searches_total",
                        kernel=KERNEL) == s0 + 1
        with open(path) as f:
            assert json.load(f)["fingerprint"] == \
                autotune.fingerprint(KERNEL)

    def test_backend_fingerprint_differs_per_kernel(self):
        assert autotune.fingerprint("lstm_cell") != \
            autotune.fingerprint("matmul_block")


# ---------------------------------------------------------------------------
# second process: warm cache performs zero measurements
# ---------------------------------------------------------------------------


_CHILD = r"""
import json, os, sys
from deeplearning4j_tpu.ops import autotune

calls = []
def factory(cfg):
    def run():
        calls.append(tuple(cfg))
    return run

got = autotune.resolve(
    "matmul_block", {"m": 8, "n": 8, "dtype": "float32"}, (4, 4),
    [(2, 2), (4, 4), (8, 8)], measure_factory=factory)

from deeplearning4j_tpu.observability.metrics import default_registry
def total(name):
    fam = default_registry().get(name)
    return 0 if fam is None else sum(c.value for c in fam.children())

print(json.dumps({
    "config": list(got),
    "measure_calls": len(calls),
    "searches": total("tuner_searches_total"),
    "hits": total("tuner_cache_hits_total"),
}))
"""


def test_second_process_with_warm_cache_measures_nothing(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "DL4J_TPU_TUNE_CACHE_DIR": str(tmp_path),
           "DL4J_TPU_TUNE": "on",
           "DL4J_TPU_TUNE_BUDGET_MS": "500"}

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _CHILD], capture_output=True,
            text=True, timeout=120, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["searches"] == 1 and cold["measure_calls"] > 0
    warm = run()  # same mode=on: the persisted entry must short-circuit
    assert warm["searches"] == 0
    assert warm["measure_calls"] == 0
    assert warm["hits"] == 1
    assert warm["config"] == cold["config"]


# ---------------------------------------------------------------------------
# trajectory: tuner on (empty cache) is bitwise tuner off
# ---------------------------------------------------------------------------


def _tiny_cnn():
    conf = (
        NeuralNetConfiguration.Builder().seed(5).learning_rate(0.1)
        .list()
        .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                stride=(1, 1), padding=(1, 1),
                                activation="relu"))
        .layer(DenseLayer(n_out=16, activation="relu"))
        .layer(OutputLayer(n_out=3))
        .set_input_type(InputType.convolutional(8, 8, 2))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _train_params(monkeypatch, tune_mode, cache_dir):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    _arm(monkeypatch, tune_mode, cache_dir=cache_dir)
    r = np.random.RandomState(3)
    data = [
        DataSet(features=r.randn(4, 2, 8, 8).astype(np.float32),
                labels=np.eye(3, dtype=np.float32)[
                    r.randint(0, 3, 4)])
        for _ in range(3)
    ]
    net = _tiny_cnn()
    net.fit(data)
    import jax

    return jax.tree_util.tree_leaves(net.params)


def test_trajectory_bitwise_identical_tuner_off_vs_cached(
        monkeypatch, tmp_path):
    """With an empty cache, cached mode resolves every kernel to the
    heuristic config — the compiled programs are IDENTICAL to tuner
    off, so training trajectories match bitwise (the acceptance
    criterion for 'tuning never changes numerics, only tiling')."""
    p_off = _train_params(monkeypatch, "off", tmp_path / "a")
    p_cached = _train_params(monkeypatch, "cached", tmp_path / "b")
    assert len(p_off) == len(p_cached)
    for a, b in zip(p_off, p_cached):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# AOT: +tuned artifacts refuse to cross the tuning boundary
# ---------------------------------------------------------------------------


def test_aot_artifact_refused_across_tuning_flip(monkeypatch,
                                                 tmp_path):
    """A step exported with tuning OFF must not install once tuning
    is active (+tuned changes the artifact kind) — and vice versa."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    r = np.random.RandomState(1)
    ds = DataSet(features=r.randn(4, 2, 8, 8).astype(np.float32),
                 labels=np.eye(3, dtype=np.float32)[
                     r.randint(0, 3, 4)])

    _arm(monkeypatch, "off")
    blob_off = _tiny_cnn().aot_export_step(ds)
    twin = _tiny_cnn()
    assert twin.aot_install_step(blob_off) is True

    _arm(monkeypatch, "cached")
    tuned = _tiny_cnn()
    assert tuned.aot_install_step(blob_off) is False
    blob_tuned = tuned.aot_export_step(ds)
    twin2 = _tiny_cnn()
    assert twin2.aot_install_step(blob_tuned) is True

    _arm(monkeypatch, "off")
    back = _tiny_cnn()
    assert back.aot_install_step(blob_tuned) is False


def test_kind_suffix_carries_tuned(monkeypatch):
    from deeplearning4j_tpu.nn import core

    monkeypatch.setenv("DL4J_TPU_PALLAS", "1")
    _arm(monkeypatch, "cached")
    net = _tiny_cnn()
    assert core.kernel_kind_suffix(net) == "+kernels+tuned"
    assert net._output_kind().endswith("+kernels+tuned")
    _arm(monkeypatch, "off")
    assert core.kernel_kind_suffix(net) == "+kernels"
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    _arm(monkeypatch, "cached")
    assert core.kernel_kind_suffix(net) == ""


# ---------------------------------------------------------------------------
# tiling: the shared divisor/candidate library
# ---------------------------------------------------------------------------


class TestTiling:
    def test_candidates_contain_heuristic(self):
        mh = tiling.pick_matmul_blocks(64, 128, 256, 4)
        assert mh in set(tiling.matmul_candidates(64, 128, 256, 4))

        bb = tiling.pick_lstm_batch_block(24, 64, 256, 4)
        assert (bb,) in set(tiling.lstm_batch_candidates(24, 64, 256,
                                                         4))

    def test_candidates_divide_their_dims(self):
        for (bb,) in tiling.lstm_batch_candidates(24, 64, 256, 4):
            assert 24 // bb * bb == 24
        for (bm, bn) in tiling.matmul_candidates(48, 64, 96, 4):
            assert 48 // bm * bm == 48 and 96 // bn * bn == 96

    def test_infeasible_returns_none_everywhere(self):
        assert tiling.pick_matmul_blocks(8, 4_000_000, 8, 4) is None
        assert tiling.matmul_candidates(8, 4_000_000, 8, 4) == []


# ---------------------------------------------------------------------------
# chaos storm: mangled cache under fire
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_mangled_tuning_cache_storm(monkeypatch, tmp_path):
    """Seeded storm over the persisted-entry failure surface: each
    round writes a valid entry, mangles it one of five ways (truncate,
    garbage, stale fingerprint, infeasible config, delete), then
    resolves in cached mode — every round must return a SAFE config
    (the heuristic, or the entry itself only when the mangle left it
    valid), bump the right fallback reason, and never crash. The storm
    closes by dispatching a real kernel against the mangled cache and
    asserting bitwise equality with tuning off."""
    rng = np.random.RandomState(CHAOS_SEED)
    actions = ("truncate", "garbage", "stale", "infeasible", "delete")
    for _ in range(20):
        action = actions[rng.randint(0, len(actions))]
        _arm(monkeypatch, "cached", cache_dir=tmp_path)
        path = _write_valid_entry(config=(8, 8))
        expect_reason = {
            "truncate": "corrupt", "garbage": "corrupt",
            "stale": "stale", "infeasible": "invalid",
            "delete": "absent",
        }[action]
        if action == "truncate":
            raw = open(path).read()
            cut = int(rng.randint(1, max(2, len(raw) - 1)))
            with open(path, "w") as f:
                f.write(raw[:cut])
            # a truncation can leave valid JSON of a smaller doc only
            # if it cut nothing; with cut < len it cannot parse+match
        elif action == "garbage":
            with open(path, "wb") as f:
                f.write(bytes(rng.randint(0, 256, 64, dtype=np.uint8)))
        elif action == "stale":
            with open(path) as f:
                doc = json.load(f)
            doc["fingerprint"] = "%032x" % rng.randint(0, 2 ** 31)
            with open(path, "w") as f:
                json.dump(doc, f)
        elif action == "infeasible":
            with open(path) as f:
                doc = json.load(f)
            doc["config"] = [3, 7]
            with open(path, "w") as f:
                json.dump(doc, f)
        else:
            os.unlink(path)
        before = _counter("tuner_fallback_total", kernel=KERNEL,
                          reason=expect_reason)
        got = _resolve()
        assert got == HEUR, (action, got)
        assert _counter("tuner_fallback_total", kernel=KERNEL,
                        reason=expect_reason) == before + 1, action

    # the cache dir is now a junkyard — real dispatch must still be
    # bitwise the tuner-off path (every lookup degrades to heuristic)
    r = np.random.RandomState(CHAOS_SEED + 1)
    x = jnp.asarray(r.randn(8, 16), jnp.float32)
    w = jnp.asarray(r.randn(16, 8) * 0.2, jnp.float32)
    b = jnp.asarray(r.randn(8) * 0.1, jnp.float32)
    _arm(monkeypatch, "cached", cache_dir=tmp_path)
    y_cached = np.asarray(matmul_block(
        x, w, b, activation="relu", interpret=pallas_interpret()))
    _arm(monkeypatch, "off")
    y_off = np.asarray(matmul_block(
        x, w, b, activation="relu", interpret=pallas_interpret()))
    np.testing.assert_array_equal(y_cached, y_off)
