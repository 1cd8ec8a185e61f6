"""Compile-artifact subsystem tests (``deeplearning4j_tpu/compile/``).

Tier 1 (persistent XLA cache): dir resolution, hit/miss accounting
into the observability registry, LRU size bounding. Tier 2 (AOT
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
