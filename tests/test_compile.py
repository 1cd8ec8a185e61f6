"""Compile-artifact subsystem tests (``deeplearning4j_tpu/compile/``).

Tier 1 (persistent XLA cache): dir resolution, hit/miss accounting
into the observability registry, LRU size bounding, and the compile
phase records (``compile_spans()``: trace, lower, compile-or-load
with the cache's outcome, mirrored into a recording tracer). Tier 2 (AOT
export): artifact framing + fingerprints, bitwise-identical restored
executables on both engines (forward AND train step), checkpoint
manifest ``artifacts`` map round-trip (old manifests still restore),
and the serving tier's warm restart: an AOT-bundled checkpoint boots
with ZERO compiles and NO jitted forward, while every
missing/stale/corrupt-artifact path degrades silently to JIT (chaos
tests — no error may reach the request path).

Isolation rule: any test that *successfully deserializes and runs*
an XLA executable (an AOT artifact or a persistent-cache hit) does
so in a SUBPROCESS. That is the honest shape of the feature — a
restart is a fresh process — and it keeps jaxlib's executable
deserialization machinery out of the long-lived test process, where
a mislinked kernel could silently corrupt unrelated tests'
numerics. In-process tests only exercise paths that load nothing
(framing, fingerprints, refusals, checkpoint byte plumbing).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.compile import persistent
from deeplearning4j_tpu.compile.aot import (
    AotArtifactError,
    artifact_fingerprint,
    install_serving_bundle,
    pack_artifact,
    peek_meta,
    serving_bucket_name,
    unpack_artifact,
)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.resilience.checkpoint import CheckpointManager

CHAOS_SEED = int(os.environ.get("DL4J_TPU_CHAOS_SEED", "1337"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared by the subprocess snippets below
_CHILD_PRELUDE = """
import json, os
import numpy as np
import jax
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.datasets.api import DataSet, MultiDataSet

def mlp_conf(seed=7):
    return (NeuralNetConfiguration.Builder().seed(seed)
            .learning_rate(0.1).list()
            .layer(DenseLayer(n_in=12, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4)).build())

def graph_conf(seed=5):
    return (NeuralNetConfiguration.Builder().seed(seed)
            .learning_rate(0.1).graph_builder().add_inputs("in")
            .add_layer("h", DenseLayer(n_in=12, n_out=8,
                                       activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=3), "h")
            .set_outputs("out").build())

def params_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(la, lb))
"""


def _run_child(snippet: str, timeout: float = 240,
               cache_dir_env=None) -> dict:
    """Run a python snippet in a FRESH process (cpu backend, no
    inherited cache knob; ``cache_dir_env`` sets the child's
    JAX_COMPILATION_CACHE_DIR) and return its one-line JSON verdict."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop(persistent.ENV_CACHE_DIR, None)
    if cache_dir_env is not None:
        env[persistent.ENV_CACHE_DIR] = cache_dir_env
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    out = subprocess.run(
        [sys.executable, "-c", _CHILD_PRELUDE + snippet],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"child failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def _mlp_conf(seed=7, n_in=12, hidden=16, n_out=4):
    return (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
        .layer(OutputLayer(n_out=n_out))
        .build()
    )


def _params_equal(a, b) -> bool:
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(la, lb)
    )


# -- artifact framing / fingerprints (in-process: loads nothing) --------


def test_artifact_framing_roundtrip():
    meta = {"kind": "output", "fingerprint": "abc"}
    data = pack_artifact(meta, b"\x00payload\xff")
    m, blob = unpack_artifact(data)
    assert m == meta and blob == b"\x00payload\xff"
    assert peek_meta(data) == meta
    with pytest.raises(AotArtifactError):
        unpack_artifact(b"NOTMAGIC" + data)
    with pytest.raises(AotArtifactError):
        unpack_artifact(data[:10])  # truncated meta
    with pytest.raises(AotArtifactError):
        unpack_artifact(None)


def test_fingerprint_sensitivity():
    base = artifact_fingerprint({"a": 1}, (8, 12), "float32", "output")
    assert base == artifact_fingerprint({"a": 1}, (8, 12), "float32",
                                        "output")
    assert base != artifact_fingerprint({"a": 2}, (8, 12), "float32",
                                        "output")
    assert base != artifact_fingerprint({"a": 1}, (4, 12), "float32",
                                        "output")
    assert base != artifact_fingerprint({"a": 1}, (8, 12), "float32",
                                        "step")
    assert base != artifact_fingerprint({"a": 1}, (8, 12), "float32",
                                        "output", backend="tpu-v9")


def test_load_artifact_refuses_stale_and_garbage():
    """Refusal paths deserialize NOTHING, so they are safe
    in-process: a stale fingerprint and undecodable bytes both come
    back None with the fallback counter bumped."""
    from deeplearning4j_tpu.compile.aot import load_artifact

    reg = MetricsRegistry()
    art = pack_artifact(
        {"fingerprint": "f" * 32, "format": "pjrt-executable",
         "kind": "output", "shape": [2, 12]}, b"never-inspected",
    )
    assert load_artifact(art, expected_fingerprint="0" * 32,
                         registry=reg) is None
    assert load_artifact(b"junk", expected_fingerprint="0" * 32,
                         registry=reg) is None
    assert reg.get("aot_fallback_total").value == 2
    assert reg.get("aot_installed_total").value == 0


def test_install_serving_bundle_ignores_foreign_blobs():
    net = MultiLayerNetwork(_mlp_conf()).init()
    installed = install_serving_bundle(net, {
        "not-an-aot-name": b"whatever",
        serving_bucket_name(2): b"garbage bytes",
    })
    assert installed == []
    assert net.aot_output_shapes() == []


# -- engine round-trips (subprocess: deserializes + runs) ---------------


def test_aot_engine_roundtrips_bitwise():
    """Export on one net, install on a fresh one, in a fresh
    process: outputs and 3-step training trajectories must be
    bitwise identical to the jitted path, the jit cache must stay
    untouched, and off-spec shapes must fall back to JIT."""
    v = _run_child("""
rng = np.random.RandomState(0)
x = rng.randn(8, 12).astype(np.float32)
ref = np.asarray(MultiLayerNetwork(mlp_conf()).init().output(x))
art = MultiLayerNetwork(mlp_conf()).init().aot_export_output((8, 12))
net = MultiLayerNetwork(mlp_conf()).init()
installed = net.aot_install_output((8, 12), art)
out = np.asarray(net.output(x))
checks = {"installed": installed}
checks["mln_bitwise"] = bool(np.array_equal(ref, out))
checks["mln_no_jit"] = net._jit_output is None
# off-spec shape transparently jits
x2 = rng.randn(3, 12).astype(np.float32)
ref2 = np.asarray(MultiLayerNetwork(mlp_conf()).init().output(x2))
checks["mln_fallback"] = bool(
    np.array_equal(ref2, np.asarray(net.output(x2))))

y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]
ds = DataSet(features=x, labels=y)
sart = MultiLayerNetwork(mlp_conf()).init().aot_export_step(ds)
a = MultiLayerNetwork(mlp_conf()).init()
b = MultiLayerNetwork(mlp_conf()).init()
checks["step_installed"] = b.aot_install_step(sart)
for _ in range(3):
    a.fit_minibatch(ds); b.fit_minibatch(ds)
checks["step_bitwise"] = params_equal(a.params, b.params)
ds2 = DataSet(features=x[:4], labels=y[:4])
a.fit_minibatch(ds2); b.fit_minibatch(ds2)
checks["step_fallback"] = params_equal(a.params, b.params)

gx = rng.randn(6, 12).astype(np.float32)
gref = np.asarray(ComputationGraph(graph_conf()).init().output(gx)[0])
gart = ComputationGraph(graph_conf()).init().aot_export_output((6, 12))
g = ComputationGraph(graph_conf()).init()
checks["g_installed"] = g.aot_install_output((6, 12), gart)
checks["g_bitwise"] = bool(
    np.array_equal(gref, np.asarray(g.output(gx)[0])))
gy = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 6)]
mds = MultiDataSet(features=[gx], labels=[gy])
gsart = ComputationGraph(graph_conf()).init().aot_export_step(mds)
ga = ComputationGraph(graph_conf()).init()
gb = ComputationGraph(graph_conf()).init()
checks["g_step_installed"] = gb.aot_install_step(gsart)
for _ in range(3):
    ga.fit_minibatch(mds); gb.fit_minibatch(mds)
checks["g_step_bitwise"] = params_equal(ga.params, gb.params)
print(json.dumps({k: bool(v) for k, v in checks.items()}))
""")
    assert v and all(v.values()), v


def test_server_restart_from_aot_bundle_zero_compiles():
    """The tentpole gate, in its honest shape (restart = fresh
    process): a server booted from an AOT-bundled checkpoint serves
    and hot-reloads with the shape-proxy compile counters flat at
    ZERO, never builds a jitted forward, and answers bitwise
    identically to a fresh jit of the same checkpoint."""
    v = _run_child("""
import tempfile
from deeplearning4j_tpu.compile.aot import export_serving_bundle
from deeplearning4j_tpu.resilience.checkpoint import CheckpointManager
from deeplearning4j_tpu.serving.batcher import pad_rows
from deeplearning4j_tpu.serving.compile_cache import jit_cache_size
from deeplearning4j_tpu.serving.server import ModelServer

d = tempfile.mkdtemp()
net = MultiLayerNetwork(mlp_conf()).init()
net.iteration_count = 1
mgr = CheckpointManager(d)
mgr.save(net, artifacts=export_serving_bundle(net, (1, 2, 4, 8)))

srv = ModelServer(checkpoint_manager=mgr, max_batch_size=8,
                  compile_cache=False).start()
rng = np.random.RandomState(3)
feats = rng.rand(3, 12).astype(np.float32)
code, body, _ = srv.submit(feats)
snap = srv.metrics_snapshot()
fresh, _ = mgr.restore_latest(load_updater=False)
want = np.asarray(fresh.output(pad_rows(feats, 4)))[:3]
bitwise = bool(np.array_equal(
    np.asarray(body["output"], np.float32), want.astype(np.float32)))
rcode, rbody = srv.reload({"force": True})  # same step would no-op
code2, _, _ = srv.submit(feats)
snap2 = srv.metrics_snapshot()
out = {
    "ok": code == 200 and rcode == 200 and code2 == 200,
    "aot_buckets": snap["compile"]["aot_buckets_installed"],
    "xla_compiles": snap["xla_compiles_total"],
    "post_warmup": snap["post_warmup_compiles_total"],
    "no_jit_forward": srv.model._jit_output is None,
    "jit_cache": jit_cache_size(srv.model),
    "bitwise": bitwise,
    "reload_aot_buckets": rbody.get("aot_buckets"),
    "xla_compiles_after_reload": snap2["xla_compiles_total"],
}
srv.stop(drain_timeout=1)
print(json.dumps(out))
""")
    assert v["ok"] and v["bitwise"]
    assert v["aot_buckets"] == 4 and v["reload_aot_buckets"] == 4
    assert v["xla_compiles"] == 0 and v["post_warmup"] == 0
    assert v["xla_compiles_after_reload"] == 0
    assert v["no_jit_forward"] is True
    assert v["jit_cache"] in (None, 0)


@pytest.mark.chaos
def test_server_stale_aot_bundle_silently_jits():
    """A bundle exported for a DIFFERENT model config (the
    stale-fingerprint case a backend/jax/architecture change
    produces) is refused artifact-by-artifact; the server warms up
    through JIT and serves — no error reaches the request path."""
    v = _run_child("""
import tempfile
from deeplearning4j_tpu.compile.aot import export_serving_bundle
from deeplearning4j_tpu.resilience.checkpoint import CheckpointManager
from deeplearning4j_tpu.serving.server import ModelServer

d = tempfile.mkdtemp()
other = MultiLayerNetwork(mlp_conf(seed=8)).init()
net = MultiLayerNetwork(mlp_conf(seed=7)).init()
net.iteration_count = 1
mgr = CheckpointManager(d)
mgr.save(net, artifacts=export_serving_bundle(other, (1, 2, 4, 8)))
srv = ModelServer(checkpoint_manager=mgr, max_batch_size=8,
                  compile_cache=False).start()
snap = srv.metrics_snapshot()
code, body, _ = srv.submit(
    np.random.RandomState(0).rand(2, 12).astype(np.float32))
out = {
    "ok": code == 200 and "output" in body,
    "aot_buckets": snap["compile"]["aot_buckets_installed"],
    "fallbacks": srv.metrics.registry.get("aot_fallback_total").value,
    "jitted": srv.metrics_snapshot()["xla_compiles_total"] > 0,
}
srv.stop(drain_timeout=1)
print(json.dumps(out))
""")
    assert v["ok"] is True
    assert v["aot_buckets"] == 0 and v["fallbacks"] == 4
    assert v["jitted"] is True


@pytest.mark.chaos
def test_server_corrupt_aot_bundle_silently_jits():
    """Both corruption flavors fall back silently: a flipped byte on
    disk (caught by the manifest CRC) and a well-CRC'd artifact
    whose payload is garbage (caught at deserialize)."""
    v = _run_child(f"""
import tempfile, pathlib
from deeplearning4j_tpu.compile.aot import (
    export_serving_bundle, pack_artifact, peek_meta,
    serving_bucket_name,
)
from deeplearning4j_tpu.resilience.checkpoint import CheckpointManager
from deeplearning4j_tpu.serving.server import ModelServer

d = tempfile.mkdtemp()
net = MultiLayerNetwork(mlp_conf()).init()
net.iteration_count = 1
bundle = export_serving_bundle(net, (1, 2, 4, 8))
crng = np.random.RandomState({CHAOS_SEED})
# valid framing + fingerprint, garbage payload: passes the manifest
# CRC, dies at deserialize
name4 = serving_bucket_name(4)
bundle[name4] = pack_artifact(peek_meta(bundle[name4]),
                              crng.bytes(512))
mgr = CheckpointManager(d)
info = mgr.save(net, artifacts=bundle)
# on-disk bit flip for another bucket: fails the manifest CRC
apath = (pathlib.Path(d)
         / info.artifacts[serving_bucket_name(2)]["file"])
raw = bytearray(apath.read_bytes())
raw[crng.randint(0, len(raw))] ^= 0xFF
apath.write_bytes(bytes(raw))
srv = ModelServer(checkpoint_manager=mgr, max_batch_size=8,
                  compile_cache=False).start()
snap = srv.metrics_snapshot()
codes = []
for rows in (1, 3, 8):
    code, body, _ = srv.submit(crng.rand(rows, 12).astype(np.float32))
    codes.append(code if "output" in body else -code)
out = {{
    "aot_buckets": snap["compile"]["aot_buckets_installed"],
    "fallbacks": srv.metrics.registry.get("aot_fallback_total").value,
    "codes": codes,
    "post_warmup":
        srv.metrics_snapshot()["post_warmup_compiles_total"],
}}
srv.stop(drain_timeout=1)
print(json.dumps(out))
""")
    # buckets 1 and 8 installed; 2 (disk CRC) and 4 (payload) fell back
    assert v["aot_buckets"] == 2
    assert v["fallbacks"] >= 1
    assert v["codes"] == [200, 200, 200]
    assert v["post_warmup"] == 0


# -- checkpoint artifacts map (in-process: plain bytes) -----------------


def test_checkpoint_artifacts_roundtrip(tmp_path):
    net = MultiLayerNetwork(_mlp_conf()).init()
    net.iteration_count = 3
    mgr = CheckpointManager(tmp_path, keep_last=1)
    info = mgr.save(net, artifacts={"aot-output-b4": b"blob-a",
                                    "extra.bin": b"blob-b"})
    assert set(info.artifacts) == {"aot-output-b4", "extra.bin"}
    # round-trips through the manifest on disk
    reread = mgr.available()[-1]
    assert reread.artifacts == info.artifacts
    assert mgr.load_artifact(reread, "aot-output-b4") == b"blob-a"
    assert mgr.load_artifacts(reread) == {"aot-output-b4": b"blob-a",
                                          "extra.bin": b"blob-b"}
    assert mgr.load_artifact(reread, "missing") is None
    # pruning removes superseded artifact files with their version
    net.iteration_count = 9
    mgr.save(net, artifacts={"aot-output-b4": b"newer"})
    leftover = [p.name for p in tmp_path.iterdir()
                if p.name.endswith(".aot")]
    assert leftover == ["checkpoint-00000009.aot-output-b4.aot"]


def test_checkpoint_old_manifest_without_artifacts_restores(tmp_path):
    net = MultiLayerNetwork(_mlp_conf()).init()
    net.iteration_count = 5
    mgr = CheckpointManager(tmp_path)
    mgr.save(net, artifacts={"aot-output-b4": b"blob"})
    # simulate a pre-artifacts manifest (schema v1 without the field)
    mpath = tmp_path / "checkpoint-00000005.json"
    doc = json.loads(mpath.read_text())
    doc.pop("artifacts")
    mpath.write_text(json.dumps(doc))
    model, info = mgr.restore_latest()
    assert info.step == 5 and info.artifacts == {}
    assert mgr.load_artifacts(info) == {}
    assert _params_equal(model.params, net.params)


@pytest.mark.chaos
def test_checkpoint_corrupted_artifact_ignored(tmp_path):
    """On-disk artifact corruption fails THAT artifact's CRC, never
    the model restore."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    net.iteration_count = 2
    mgr = CheckpointManager(tmp_path)
    info = mgr.save(net, artifacts={"aot-output-b4": b"x" * 256})
    apath = tmp_path / info.artifacts["aot-output-b4"]["file"]
    raw = bytearray(apath.read_bytes())
    raw[CHAOS_SEED % len(raw)] ^= 0xFF
    apath.write_bytes(bytes(raw))
    assert mgr.load_artifact(info, "aot-output-b4") is None
    model, info2 = mgr.restore_latest()  # model restore unaffected
    assert info2.step == 2
    assert _params_equal(model.params, net.params)


# -- tier 1: persistent cache -------------------------------------------


@pytest.mark.parametrize("env,platform,expect", [
    ("/somewhere/cache", "cpu", "/somewhere/cache"),
    ("/somewhere/cache", "tpu", "/somewhere/cache"),
    (None, "tpu", os.path.join(REPO, ".jax_cache")),
    # unset on the CPU: disabled by default (operator opt-in)
    (None, "cpu", None),
])
def test_default_cache_dir_resolution(monkeypatch, env, platform,
                                      expect):
    """JAX's own variable places the cache; without it the one fixed
    path inside the checkout, and only where the backend is a TPU —
    chosen from the observed platform, steered here in the test."""
    from deeplearning4j_tpu.ops import dispatch

    if env is None:
        monkeypatch.delenv(persistent.ENV_CACHE_DIR, raising=False)
    else:
        monkeypatch.setenv(persistent.ENV_CACHE_DIR, env)
    monkeypatch.setattr(dispatch, "effective_platform",
                        lambda: platform)
    assert persistent.ENV_CACHE_DIR == "JAX_COMPILATION_CACHE_DIR"
    assert persistent.default_cache_dir() == expect


_CACHE_DIR_CHILD = """
from deeplearning4j_tpu.compile import persistent
from deeplearning4j_tpu.ops import dispatch

dispatch.effective_platform = lambda: "tpu"  # no chip here: steer it
before = jax.config.jax_compilation_cache_dir
got = persistent.enable_persistent_cache(%r)
print(json.dumps({
    "before": before, "got": got,
    "after": jax.config.jax_compilation_cache_dir,
    "default": persistent.default_cache_dir(),
}))
"""


def _cache_dir_child(env_dir, arg_dir) -> dict:
    """``enable_persistent_cache(arg_dir)`` in a fresh process whose
    JAX_COMPILATION_CACHE_DIR is ``env_dir`` (None: unset)."""
    return _run_child(_CACHE_DIR_CHILD % (arg_dir,),
                      cache_dir_env=env_dir)


def test_env_cache_dir_wins_and_jax_config_is_untouched(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is used and
    no other is set in code: JAX already holds it, an argument naming
    another directory loses, and that other directory is never made."""
    env_dir = str(tmp_path / "from_env")
    other = str(tmp_path / "from_arg")
    v = _cache_dir_child(env_dir, other)
    assert v["before"] == env_dir  # jax read its own variable
    assert v["got"] == env_dir
    assert v["after"] == env_dir
    assert v["default"] == env_dir
    assert os.path.isdir(env_dir) and not os.path.exists(other)


def test_unset_cache_dir_is_repo_path_in_every_process():
    """Unset, the cache is <repo>/.jax_cache — a fixed path, so two
    processes of one checkout share entries (the path is part of the
    key; a temp dir, a pid or a time in it would never hit)."""
    want = os.path.join(REPO, ".jax_cache")
    assert persistent.REPO_CACHE_DIR == want
    for _ in range(2):
        v = _cache_dir_child(None, None)
        assert v["before"] is None
        assert v["default"] == want
        assert v["got"] == want and v["after"] == want


def test_persistent_cache_hits_misses_and_counters():
    """Miss-then-hit across two identical programs, in a subprocess
    (a cache hit deserializes an executable). Counters land in the
    registry; the second compile comes from disk, not the backend."""
    v = _run_child("""
import tempfile
from deeplearning4j_tpu.compile import persistent
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
import jax.numpy as jnp

reg = MetricsRegistry()
d = persistent.enable_persistent_cache(tempfile.mkdtemp(),
                                       registry=reg)
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)

def make():
    # identical lambdas hash to the SAME cache key; each jax.jit
    # object is new, so the in-process jit cache can't answer the
    # second compile
    return jax.jit(lambda v: (v * 3.5 + 1.0) @ v.T)

before = persistent.cache_stats()
r1 = np.asarray(make()(x))
mid = persistent.cache_stats()
r2 = np.asarray(make()(x))
after = persistent.cache_stats()
print(json.dumps({
    "enabled": d is not None and bool(os.listdir(d)),
    "miss_counted": mid["misses"] > before["misses"],
    "compile_counted":
        mid["backend_compiles"] > before["backend_compiles"],
    "hit_counted": after["hits"] > mid["hits"],
    "second_from_disk":
        after["backend_compiles"] == mid["backend_compiles"],
    "bitwise": bool(np.array_equal(r1, r2)),
    "reg_hits": reg.get("compile_cache_hits_total").value,
    "reg_misses": reg.get("compile_cache_misses_total").value,
    "reg_calls": reg.get("xla_compile_or_load_total").value,
}))
""")
    for key in ("enabled", "miss_counted", "compile_counted",
                "hit_counted", "second_from_disk", "bitwise"):
        assert v[key] is True, (key, v)
    assert v["reg_hits"] >= 1 and v["reg_misses"] >= 1
    assert v["reg_calls"] >= 2


def test_bound_cache_size(tmp_path):
    for i in range(8):
        p = tmp_path / f"entry-{i}-cache"
        p.write_bytes(b"z" * 100)
        os.utime(p, (1000 + i, 1000 + i))  # staggered LRU order
    removed = persistent.bound_cache_size(tmp_path, 350)
    assert removed == 500  # five oldest go; three newest stay
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == ["entry-5-cache", "entry-6-cache", "entry-7-cache"]
    # under the bound: nothing to do
    assert persistent.bound_cache_size(tmp_path, 1 << 20) == 0


def test_enable_persistent_cache_cpu_unset_returns_none(monkeypatch):
    """On the CPU with nothing named the cache stays off, and jax's
    configuration is left as it was."""
    import jax

    monkeypatch.delenv(persistent.ENV_CACHE_DIR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert persistent.enable_persistent_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


# -- compile phase records (compile_spans(), PR 39) ---------------------


@pytest.fixture
def fresh_phase_records(monkeypatch):
    """The process's compile accounting with an empty ring of its own,
    the listeners installed; the real one comes back afterwards."""
    monkeypatch.setattr(persistent, "_stats", persistent._CacheStats())
    monkeypatch.setattr(persistent, "_registry_sinks", [])
    persistent.install_cache_accounting(MetricsRegistry())
    return persistent


def _union(intervals):
    total, edge = 0.0, None
    for s, e in sorted(intervals):
        if edge is None or s > edge:
            total, edge = total + e - s, e
        elif e > edge:
            total, edge = total + e - edge, e
    return total


def _nested_jits():
    import jax
    import jax.numpy as jnp

    def pr39_inner(x):
        return jnp.tanh(x) * 3.0

    inner = jax.jit(pr39_inner)

    def pr39_outer(x):
        return inner(x) + 1.0

    return jax.jit(pr39_outer), jnp.arange(6, dtype=jnp.float32)


def test_a_jit_traced_inside_another_is_folded_into_its_record(
        fresh_phase_records):
    outer, x = _nested_jits()
    outer(x).block_until_ready()
    traces = [r for r in fresh_phase_records.compile_spans()
              if r["name"] == "compile.trace"]
    funs = [r["attrs"]["fun"] for r in traces]
    # one record for the outer function; the inner one's trace ran
    # inside it and is counted there, so the union is the outer's
    assert funs.count("pr39_outer") == 1 and "pr39_inner" not in funs
    rec, = [r for r in traces if r["attrs"]["fun"] == "pr39_outer"]
    assert rec["attrs"]["nested"] >= 1
    assert rec["attrs"]["nested_s"] > 0  # every level's seconds, summed
    spans = [(r["start"], r["end"]) for r in traces]
    assert _union(spans) < sum(e - s + r["attrs"].get("nested_s", 0.0)
                               for (s, e), r in zip(spans, traces))
    for r in traces:  # the shape of Span.to_dict()
        assert {"name", "start", "end", "attrs", "trace_id", "span_id",
                "parent_id"} <= set(r)
        assert r["end"] >= r["start"] and r["parent_id"] is None


def test_only_the_same_threads_traces_fold(monkeypatch):
    import threading

    monkeypatch.setattr(persistent, "_stats", persistent._CacheStats())
    stats = persistent._stats
    # another thread's trace inside the interval stays a record
    other = threading.Thread(target=stats.keep, args=(
        "compile.trace", 11.0, 12.0, {"fun": "elsewhere"}))
    other.start()
    other.join()
    stats.keep("compile.trace", 10.5, 11.5, {"fun": "callee"})
    stats.keep("compile.trace", 12.2, 12.4, {"fun": "callee2"})
    stats.keep("compile.trace", 10.0, 13.0, {"fun": "caller"})
    # held until the thread lowers, and read all the same
    held = {r["attrs"]["fun"]: r for r in persistent.compile_spans()}
    assert set(held) == {"elsewhere", "caller"}
    assert held["caller"]["attrs"]["nested"] == 2
    assert held["caller"]["attrs"]["nested_s"] == pytest.approx(1.2)
    # a lowering rule's own trace is the lowering's
    stats.keep("compile.trace", 13.2, 13.3, {"fun": "rule"})
    final = stats.keep("compile.lower", 13.0, 14.0, {"fun": "jit(caller)"})
    assert [r[0] for r in final] == ["compile.trace", "compile.lower"]
    assert final[1][3] == {"fun": "jit(caller)", "nested": 1,
                           "nested_s": pytest.approx(0.1)}
    # the compile's record takes in nothing
    stats.keep("compile.trace", 14.2, 14.3, {"fun": "after"})
    final = stats.keep("compile.backend", 14.0, 15.0, {"fun": "jit(c)"})
    assert [r[3]["fun"] for r in final] == ["after", "jit(c)"]
    assert len(stats.spans) == 4


def test_lower_and_backend_records_name_the_jit(fresh_phase_records):
    import time

    outer, x = _nested_jits()
    t0 = time.perf_counter()
    outer(x).block_until_ready()
    t1 = time.perf_counter()
    recs = fresh_phase_records.compile_spans()
    lower = [r for r in recs if r["name"] == "compile.lower"]
    backend = [r for r in recs if r["name"] == "compile.backend"]
    assert "jit(pr39_outer)" in [r["attrs"]["fun"] for r in lower]
    mine, = [r for r in backend if r["attrs"]["fun"] == "jit(pr39_outer)"]
    assert mine["attrs"]["outcome"] in ("hit", "miss", "uncached")
    # on the clock of the fit drivers' spans, inside the call
    assert t0 <= mine["start"] <= mine["end"] <= t1
    # the inner jit is inlined: lowered and compiled as part of outer
    assert "jit(pr39_inner)" not in [r["attrs"]["fun"] for r in backend]


def test_persistent_cache_outcome_of_each_backend_record():
    """First compile a miss, after ``jax.clear_caches()`` the same
    program a hit with its retrieval time; no ``xla.compile.cache``
    span, while a recording tracer gets the phases. In a subprocess:
    a hit deserializes an executable."""
    v = _run_child("""
import tempfile
from deeplearning4j_tpu.compile import persistent
from deeplearning4j_tpu.observability.trace import Tracer, set_global_tracer
import jax.numpy as jnp

persistent.enable_persistent_cache(tempfile.mkdtemp())
tracer = Tracer()
set_global_tracer(tracer)

def pr39_cached(v):
    return (v * 2.5 - 1.0) @ v.T

x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
jax.jit(pr39_cached)(x).block_until_ready()
jax.clear_caches()
jax.jit(pr39_cached)(x).block_until_ready()
mine = [r["attrs"] for r in persistent.compile_spans()
        if r["name"] == "compile.backend"
        and r["attrs"]["fun"] == "jit(pr39_cached)"]
print(json.dumps({
    "backend": mine,
    "tracer_names": sorted({s.name for s in tracer.finished_spans()}),
}))
""")
    first, second = v["backend"]
    assert first["outcome"] == "miss" and "retrieval_s" not in first
    assert second["outcome"] == "hit" and second["retrieval_s"] >= 0
    assert "xla.compile.cache" not in v["tracer_names"]
    assert {"compile.trace", "compile.lower",
            "compile.backend"} <= set(v["tracer_names"])


def test_a_trace_with_more_callees_than_the_ring_is_one_record(
        monkeypatch):
    monkeypatch.setattr(persistent, "_stats", persistent._CacheStats())
    stats = persistent._stats
    n = persistent.MAX_COMPILE_SPANS + 100  # a step's direct callees
    for i in range(n):
        stats.keep("compile.trace", 1.0 + i, 1.5 + i, {"fun": f"op{i}"})
    stats.keep("compile.trace", 0.5, n + 2.0, {"fun": "multi_step"})
    stats.keep("compile.lower", n + 2.0, n + 3.0, {"fun": "jit(step)"})
    kept = persistent.compile_spans()
    assert [r["attrs"]["fun"] for r in kept] == ["multi_step", "jit(step)"]
    assert kept[0]["attrs"]["nested"] == n
    assert kept[0]["attrs"]["nested_s"] == pytest.approx(0.5 * n)
    assert persistent.cache_stats()["compile_spans_dropped"] == 0
    # a thread that traces and never lowers holds a bounded number
    monkeypatch.setattr(persistent, "MAX_HELD_TRACES", 8)
    for i in range(10):
        stats.keep("compile.trace", 1e6 + i, 1e6 + i + 0.5, {"fun": "t"})
    assert len(stats.spans) == 2 + 2
    assert len(persistent.compile_spans()) == 4 + 8


def test_the_phase_ring_holds_its_bound(monkeypatch):
    assert persistent._CacheStats().spans.maxlen \
        == persistent.MAX_COMPILE_SPANS == 4096
    monkeypatch.setattr(persistent, "MAX_COMPILE_SPANS", 64)
    monkeypatch.setattr(persistent, "_stats", persistent._CacheStats())
    for i in range(64 + 10):
        persistent._on_span(
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            100.0, 100.001, fun_name=f"f{i}")
    # an event that is not a compile phase keeps nothing
    persistent._on_span("/jax/other/duration", 0.0, 1.0, fun_name="x")
    kept = persistent.compile_spans()
    assert len(kept) == 64
    assert kept[0]["attrs"]["fun"] == "f10"  # the oldest were dropped
    assert kept[-1]["attrs"]["fun"] == "f73"
    assert persistent.cache_stats()["compile_spans_dropped"] == 10


def test_phases_become_tracer_spans_only_while_it_records(
        fresh_phase_records):
    from deeplearning4j_tpu.observability import trace

    recording = trace.Tracer()
    prev = trace.set_global_tracer(recording)
    try:
        outer, x = _nested_jits()
        outer(x).block_until_ready()
    finally:
        trace.set_global_tracer(prev)
    got = {(s.name, s.attrs.get("fun")): s
           for s in recording.finished_spans()}
    for key in [("compile.trace", "pr39_outer"),
                ("compile.lower", "jit(pr39_outer)"),
                ("compile.backend", "jit(pr39_outer)")]:
        span = got[key]
        assert span.end_time is not None and span.end_time >= span.start_time
    assert "outcome" in got[("compile.backend", "jit(pr39_outer)")].attrs
    assert "xla.compile.cache" not in {n for n, _ in got}
    # the records and the spans are the same intervals
    rec, = [r for r in fresh_phase_records.compile_spans()
            if r["name"] == "compile.trace"
            and r["attrs"]["fun"] == "pr39_outer"]
    assert got[("compile.trace", "pr39_outer")].start_time == rec["start"]

    # the default tracer (off, following a profiler session that is not
    # running) keeps none of them, and the records are kept all the same
    default = trace.get_tracer()
    before = len(default.finished_spans())
    n_before = len(fresh_phase_records.compile_spans())
    outer2, x2 = _nested_jits()
    outer2(x2).block_until_ready()
    assert len(default.finished_spans()) == before
    assert len(fresh_phase_records.compile_spans()) > n_before
    assert default.record("compile.trace", 0.0, 1.0) is None


def test_no_compile_cache_event_reaches_the_tracer(fresh_phase_records):
    from deeplearning4j_tpu.observability import trace

    recording = trace.Tracer()
    prev = trace.set_global_tracer(recording)
    try:
        outer, x = _nested_jits()
        outer(x).block_until_ready()
        # the cache's own verdicts, as jax reports them
        fresh_phase_records._on_event(persistent._EV_MISS)
        fresh_phase_records._on_event(persistent._EV_HIT)
    finally:
        trace.set_global_tracer(prev)
    names = {s.name for s in recording.finished_spans()}
    assert "compile.backend" in names
    assert not any(n.startswith("xla.compile") for n in names)


def test_a_recording_tracer_keeps_its_fit_tree_through_a_large_compile(
        fresh_phase_records):
    """A model's step traces thousands of nested functions: folded,
    they reach a default-sized tracer as one span, so the ``fit`` tree
    recorded before them is still in its ring."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.observability import trace

    recording = trace.Tracer()  # the default ring, 2,048 spans
    prev = trace.set_global_tracer(recording)
    try:
        with recording.start_span("fit") as fit:
            recording.start_span("fit.dispatch", parent=fit).end()

        def leaf(i):
            def f(v):
                return v * (i + 1.0)
            f.__name__ = f"pr39_leaf{i}"
            return jax.jit(f)

        leaves = [leaf(i) for i in range(1100)]

        def pr39_model(v):
            for g in leaves:
                v = g(v)
            return v

        jax.jit(pr39_model)(jnp.ones(4)).block_until_ready()
    finally:
        trace.set_global_tracer(prev)
    rec, = [r for r in fresh_phase_records.compile_spans()
            if r["attrs"].get("fun") == "pr39_model"]
    assert rec["attrs"]["nested"] > recording._finished.maxlen
    names = [s.name for s in recording.finished_spans()]
    assert names[:2] == ["fit.dispatch", "fit"]
    assert names.count("compile.trace") < 10
