"""Preemption-notice chaos storms (registered in
``scripts/run_chaos.sh``).

The platform delivers SIGTERM with a short grace window before a
preemptible host vanishes; ``resilience/preemption.py`` turns that
into a drained emergency checkpoint at the next step boundary. These
storms assert the whole contract:

- simulated notice (``PreemptionHandler.notify`` — chaos-injectable,
  identical consequences to the signal) mid-fit with prefetch + async
  dispatch live -> emergency checkpoint, and the resumed run is
  bitwise trajectory-equivalent to the uninterrupted one, on BOTH
  engines;
- a REAL SIGTERM against a training subprocess mid-epoch -> exit code
  75 (``EXIT_PREEMPTED``) with a restorable checkpoint behind it;
- ``ContinualTrainer`` publishes its emergency checkpoint through its
  own ``publish()`` (AOT artifacts attached);
- ``ModelServer`` + ``ServingRouter`` translate the signal into the
  graceful drain: zero 5xx across an in-flight load while one backend
  is SIGTERM'd (subprocess-based).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import conftest

from test_resilience import (
    assert_updater_state_match,
    batches as mk_batches,
    simple_net,
)

from deeplearning4j_tpu.datasets.api import DataSet, ListDataSetIterator
from deeplearning4j_tpu.exceptions import DL4JFaultException
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import DistributedTrainer
from deeplearning4j_tpu.resilience import (
    EXIT_PREEMPTED,
    CheckpointManager,
    PreemptedException,
    PreemptionHandler,
    exit_on_preemption,
    preemption_requested,
)
from deeplearning4j_tpu.resilience.preemption import active_handler

CHAOS_SEED = int(os.environ.get("DL4J_TPU_CHAOS_SEED", "1337"))
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def graph_net(seed=7, lr=0.05):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(lr)
        .updater("ADAM")
        .graph_builder()
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_in=4, n_out=8,
                                   activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_in=8, n_out=3), "d")
        .set_outputs("out")
        .build()
    )
    return ComputationGraph(conf).init()


class NotifyAt:
    """IterationListener that fires the simulated preemption notice
    once, at optimizer step ``at``."""

    def __init__(self, at):
        self.at = at
        self.fired = False

    def iteration_done(self, model, it):
        if it == self.at and not self.fired:
            self.fired = True
            active_handler().notify("chaos")


# -- handler unit behavior ----------------------------------------------


def test_handler_install_uninstall_restores_dispositions():
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    h = PreemptionHandler()
    assert not preemption_requested()
    with h:
        assert active_handler() is h
        assert signal.getsignal(signal.SIGTERM) != prev_term
        h.notify("simulated")
        assert h.requested and preemption_requested()
        assert h.reason == "simulated"
    assert active_handler() is None
    assert signal.getsignal(signal.SIGTERM) == prev_term
    assert signal.getsignal(signal.SIGINT) == prev_int


def test_callbacks_run_on_notice_and_late_registration():
    h = PreemptionHandler()
    seen = []
    h.on_preemption(lambda reason: seen.append(("early", reason)))
    h.notify("chaos")
    deadline = time.monotonic() + 5
    while len(seen) < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert seen == [("early", "chaos")]
    # registered after the notice: runs immediately, same reason
    h.on_preemption(lambda reason: seen.append(("late", reason)))
    assert seen[-1] == ("late", "chaos")
    # repeat notices are idempotent
    h.notify("again")
    time.sleep(0.05)
    assert len(seen) == 2


def test_exit_on_preemption_exit_codes(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    m = simple_net()
    m.fit_minibatch(mk_batches(np.random.RandomState(0), 1)[0])
    h = PreemptionHandler(manager=mgr)
    h.notify("chaos")
    with pytest.raises(SystemExit) as e:
        with exit_on_preemption():
            h.emergency_stop(m)
    assert e.value.code == EXIT_PREEMPTED  # checkpoint landed
    h2 = PreemptionHandler()  # no manager: nothing durable
    h2.notify("chaos")
    with pytest.raises(SystemExit) as e2:
        with exit_on_preemption():
            h2.emergency_stop(m)
    assert e2.value.code == 76  # EXIT_PREEMPTED_DIRTY


# -- simulated-notice storms: both engines, prefetch + dispatch live ----


@pytest.mark.chaos
def test_chaos_notice_mid_epoch_distributed_prefetch_bitwise_resume(
    tmp_path,
):
    """DistributedTrainer.fit with prefetch + async dispatch live:
    the notice lands mid-epoch, the window drains, the prefetch
    worker joins, the emergency checkpoint is written — and the
    resumed run replays the uninterrupted trajectory bitwise."""
    rng = np.random.RandomState(CHAOS_SEED)
    bs = mk_batches(rng, n_batches=10)
    mgr = CheckpointManager(str(tmp_path))

    m = simple_net()
    tr = DistributedTrainer(m)
    m.listeners.append(NotifyAt(5))
    with PreemptionHandler(manager=mgr):
        with pytest.raises(PreemptedException) as exc:
            tr.fit(ListDataSetIterator(bs), epochs=2, prefetch=2)
    assert exc.value.checkpoint is not None
    assert exc.value.checkpoint.step == 5
    assert exc.value.exit_code == EXIT_PREEMPTED
    assert mgr.latest_step() == 5

    survivor = simple_net()
    tr2 = DistributedTrainer(survivor)
    step = tr2.resume(mgr)
    assert step == 5
    tr2.fit(ListDataSetIterator(bs[step:]), epochs=1, prefetch=2)
    tr2.fit(ListDataSetIterator(bs), epochs=1, prefetch=2)

    full = simple_net()
    DistributedTrainer(full).fit(ListDataSetIterator(bs), epochs=2,
                                 prefetch=2)
    conftest.assert_params_match(survivor, full)
    assert_updater_state_match(survivor, full)
    assert survivor.iteration_count == full.iteration_count == 20


@pytest.mark.chaos
def test_chaos_notice_mid_epoch_graph_engine_bitwise_resume(tmp_path):
    """Same storm through the graph engine's own fit driver
    (``nn/core.fit_batches``): the step-boundary check covers both
    engines via the unified core."""
    rng = np.random.RandomState(CHAOS_SEED + 1)
    bs = mk_batches(rng, n_batches=10)
    mgr = CheckpointManager(str(tmp_path))

    g = graph_net()
    g.listeners.append(NotifyAt(4))
    with PreemptionHandler(manager=mgr):
        with pytest.raises(PreemptedException) as exc:
            g.fit(ListDataSetIterator(bs), epochs=2)
    assert exc.value.checkpoint.step == 4
    assert mgr.latest_step() == 4

    from deeplearning4j_tpu.resilience.checkpoint import restore_into

    survivor = graph_net()
    _, step = restore_into(survivor, mgr)
    assert step == 4
    survivor.fit(ListDataSetIterator(bs[step:]), epochs=1)
    survivor.fit(ListDataSetIterator(bs), epochs=1)

    full = graph_net()
    full.fit(ListDataSetIterator(bs), epochs=2)
    conftest.assert_params_match(survivor, full)
    assert_updater_state_match(survivor, full)
    assert survivor.iteration_count == full.iteration_count == 20


@pytest.mark.chaos
def test_chaos_notice_continual_trainer_emergency_publish(tmp_path):
    """The continual trainer's emergency checkpoint goes through its
    own publish(): versioned, journal-compatible, AOT artifacts
    attached."""
    from deeplearning4j_tpu.loop import ContinualTrainer

    rng = np.random.RandomState(CHAOS_SEED + 2)
    bs = mk_batches(rng, n_batches=12)
    mgr = CheckpointManager(str(tmp_path))
    m = simple_net()
    ct = ContinualTrainer(
        m, mgr, publish_every=100,  # only the emergency publish fires
        artifact_fn=lambda model: {
            "aot-output-b4": b"stub-executable-bytes",
        },
    )
    m.listeners.append(NotifyAt(3))
    with PreemptionHandler():
        with pytest.raises(PreemptedException) as exc:
            ct.run(ListDataSetIterator(bs))
    info = exc.value.checkpoint
    assert info is not None and info.step == 3
    assert "aot-output-b4" in info.artifacts
    assert mgr.load_artifact(info, "aot-output-b4") == (
        b"stub-executable-bytes"
    )
    assert ct.last_published.step == 3


@pytest.mark.chaos
def test_chaos_notice_early_stopping_checkpoints_and_raises(tmp_path):
    from deeplearning4j_tpu.earlystopping import (
        DataSetLossCalculator,
        EarlyStoppingConfiguration,
        EarlyStoppingTrainer,
        MaxEpochsTerminationCondition,
    )

    rng = np.random.RandomState(CHAOS_SEED + 3)
    data = mk_batches(rng, n_batches=4)
    mgr = CheckpointManager(str(tmp_path))
    net = simple_net()
    cfg = EarlyStoppingConfiguration(
        score_calculator=DataSetLossCalculator(
            ListDataSetIterator(data)),
        epoch_terminations=[MaxEpochsTerminationCondition(5)],
        checkpoint_manager=mgr,
    )
    net.listeners.append(NotifyAt(6))  # mid second epoch
    with PreemptionHandler():
        with pytest.raises(PreemptedException) as exc:
            EarlyStoppingTrainer(cfg, net,
                                 ListDataSetIterator(data)).fit()
    assert exc.value.checkpoint.step == 6
    assert mgr.latest_step() == 6  # on top of the per-epoch step 4


@pytest.mark.chaos
def test_chaos_emergency_stop_survives_pending_prefetch_fault(tmp_path):
    """Satellite contract: the emergency path shuts the prefetch
    worker down with a bounded join and a PENDING worker fault does
    not cost the checkpoint — it is chained onto the
    PreemptedException instead."""
    from deeplearning4j_tpu.datasets.prefetch import PrefetchIterator

    rng = np.random.RandomState(CHAOS_SEED + 4)
    bs = mk_batches(rng, n_batches=6)

    def feed():
        yield from bs[:2]
        raise OSError("source died after the notice")

    class Flaky:
        def __iter__(self):
            return feed()

        def reset(self):
            pass

    pf = PrefetchIterator(Flaky(), queue_depth=1)
    assert pf.has_next()
    mgr = CheckpointManager(str(tmp_path))
    m = simple_net()
    m.fit_minibatch(pf.next())
    h = PreemptionHandler(manager=mgr)
    h.notify("chaos")
    # give the worker time to hit the fault and park it as pending
    time.sleep(0.2)
    with pytest.raises(PreemptedException) as exc:
        h.emergency_stop(m, prefetch=pf)
    assert exc.value.checkpoint is not None  # checkpoint still landed
    assert isinstance(exc.value.__cause__, DL4JFaultException)
    assert pf._thread is None  # worker joined


# -- the real signal: SIGTERM against a training subprocess -------------


_TRAIN_CHILD = r"""
import os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeplearning4j_tpu.datasets.api import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import DistributedTrainer
from deeplearning4j_tpu.resilience import (
    CheckpointManager, PreemptionHandler, exit_on_preemption,
)

mode, ckpt_dir, out_path = sys.argv[1], sys.argv[2], sys.argv[3]
N = 30

def net():
    conf = (NeuralNetConfiguration.Builder().seed(7)
            .learning_rate(0.05).updater("ADAM").list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3)).build())
    return MultiLayerNetwork(conf).init()

def batches():
    rng = np.random.RandomState(int(os.environ.get(
        "DL4J_TPU_CHAOS_SEED", "1337")))
    return [DataSet(
        features=rng.randn(8, 4).astype(np.float32),
        labels=np.eye(3)[rng.randint(0, 3, 8)].astype(np.float32),
    ) for _ in range(N)]

class Paced:
    # slow source so the parent's SIGTERM lands mid-epoch with the
    # prefetch worker and the dispatch window both live
    def __init__(self, items):
        self.items = items
    def __iter__(self):
        for ds in self.items:
            time.sleep(0.05)
            yield ds
    def reset(self):
        pass

m = net()
tr = DistributedTrainer(m)
mgr = CheckpointManager(ckpt_dir)
bs = batches()
if mode == "train":
    class Progress:
        def iteration_done(self, model, it):
            print(f"step {it}", flush=True)
    m.listeners.append(Progress())
    PreemptionHandler(manager=mgr).install()
    with exit_on_preemption():
        tr.fit(Paced(bs), epochs=1, prefetch=2)
elif mode == "resume":
    step = tr.resume(mgr)
    print(f"resumed {step}", flush=True)
    tr.fit(ListDataSetIterator(bs[step:]), epochs=1)
else:  # full
    tr.fit(ListDataSetIterator(bs), epochs=1)
flat = {f"{ln}/{pn}": np.asarray(a)
        for ln, lp in m.params.items() for pn, a in lp.items()}
np.savez(out_path, step=m.iteration_count, **flat)
"""


def _run_child(mode, ckpt_dir, out_path, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    return subprocess.run(
        [sys.executable, "-c", _TRAIN_CHILD, mode, ckpt_dir, out_path],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=timeout,
    )


@pytest.mark.chaos
def test_chaos_sigterm_mid_epoch_exit_code_and_bitwise_resume(tmp_path):
    """The real signal: SIGTERM a training process mid-epoch
    (prefetch + async dispatch live). It must exit with the
    documented code 75 leaving an emergency checkpoint, and a fresh
    process resuming from it must finish bitwise-identical to an
    uninterrupted run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    ckpt = str(tmp_path / "ckpt")
    p = subprocess.Popen(
        [sys.executable, "-c", _TRAIN_CHILD, "train", ckpt,
         str(tmp_path / "train.npz")],
        env=env, cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True,
    )
    try:
        seen = 0
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            line = p.stdout.readline()
            if line.startswith("step "):
                seen = int(line.split()[1])
                if seen >= 3:
                    break
        assert seen >= 3, "trainer never reached step 3"
        os.kill(p.pid, signal.SIGTERM)  # the storm
        rc = p.wait(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == EXIT_PREEMPTED, f"exit code {rc}, wanted 75"

    mgr = CheckpointManager(ckpt)
    step = mgr.latest_step()
    assert step is not None and step >= 3

    r = _run_child("resume", ckpt, str(tmp_path / "resume.npz"))
    assert r.returncode == 0, r.stderr[-2000:]
    f = _run_child("full", str(tmp_path / "unused"),
                   str(tmp_path / "full.npz"))
    assert f.returncode == 0, f.stderr[-2000:]

    resumed = np.load(tmp_path / "resume.npz")
    full = np.load(tmp_path / "full.npz")
    assert int(resumed["step"]) == int(full["step"]) == 30
    for key in full.files:
        np.testing.assert_array_equal(
            resumed[key], full[key], err_msg=key,
        )


_MEGA_CHILD = r"""
import os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeplearning4j_tpu.datasets.api import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import core
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import DistributedTrainer
from deeplearning4j_tpu.resilience import (
    CheckpointManager, PreemptionHandler, exit_on_preemption,
)

mode, ckpt_dir, out_path = sys.argv[1], sys.argv[2], sys.argv[3]
N, K = 30, 3

def net():
    conf = (NeuralNetConfiguration.Builder().seed(7)
            .learning_rate(0.05).updater("ADAM").list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3)).build())
    return MultiLayerNetwork(conf).init()

def batches():
    rng = np.random.RandomState(int(os.environ.get(
        "DL4J_TPU_CHAOS_SEED", "1337")))
    return [DataSet(
        features=rng.randn(8, 4).astype(np.float32),
        labels=np.eye(3)[rng.randint(0, 3, 8)].astype(np.float32),
    ) for _ in range(N)]

class Paced:
    # slow source so the parent's SIGTERM lands mid-chunk, between
    # two megastep dispatches
    def __init__(self, items):
        self.items = items
    def __iter__(self):
        for ds in self.items:
            time.sleep(0.05)
            yield ds
    def reset(self):
        pass

m = net()
tr = DistributedTrainer(m)
mgr = CheckpointManager(ckpt_dir)
bs = batches()
if mode == "train":
    class Progress:
        supports_batched_iterations = True
        def iteration_done(self, model, it):
            print(f"step {it}", flush=True)
    m.listeners.append(Progress())
    core.set_transforms(m, megastep=K)
    assert core.can_megastep(m), "storm must exercise the fused path"
    PreemptionHandler(manager=mgr).install()
    with exit_on_preemption():
        tr.fit(Paced(bs), epochs=1)
elif mode == "resume":
    step = tr.resume(mgr)
    print(f"resumed {step}", flush=True)
    tr.fit(ListDataSetIterator(bs[step:]), epochs=1, megastep=K)
else:  # full
    tr.fit(ListDataSetIterator(bs), epochs=1, megastep=K)
flat = {f"{ln}/{pn}": np.asarray(a)
        for ln, lp in m.params.items() for pn, a in lp.items()}
np.savez(out_path, step=m.iteration_count, **flat)
"""


def _run_mega_child(mode, ckpt_dir, out_path, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    return subprocess.run(
        [sys.executable, "-c", _MEGA_CHILD, mode, ckpt_dir, out_path],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=timeout,
    )


@pytest.mark.chaos
def test_chaos_sigterm_megastep_chunk_boundary_bitwise_resume(tmp_path):
    """SIGTERM a training process with ``megastep=3`` live, mid-chunk.
    The emergency checkpoint must land on the LAST CHUNK BOUNDARY —
    a step multiple of K, staleness bounded by K-1: the un-flushed
    buffer holds no dispatched work, so nothing between boundaries
    needs saving — and a fresh megastep process resuming from it must
    finish bitwise-identical to an uninterrupted megastep run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    ckpt = str(tmp_path / "ckpt")
    p = subprocess.Popen(
        [sys.executable, "-c", _MEGA_CHILD, "train", ckpt,
         str(tmp_path / "train.npz")],
        env=env, cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True,
    )
    try:
        seen = 0
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            line = p.stdout.readline()
            if line.startswith("step "):
                seen = int(line.split()[1])
                if seen >= 3:
                    break
        assert seen >= 3, "trainer never finished the first chunk"
        os.kill(p.pid, signal.SIGTERM)  # the storm, mid-chunk
        rc = p.wait(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == EXIT_PREEMPTED, f"exit code {rc}, wanted 75"

    mgr = CheckpointManager(ckpt)
    step = mgr.latest_step()
    assert step is not None and step >= 3
    # the chunk-boundary contract: only dispatched chunks are
    # durable, so the checkpoint step is a multiple of K=3
    assert step % 3 == 0, (
        f"emergency checkpoint at step {step}, not a chunk boundary"
    )

    r = _run_mega_child("resume", ckpt, str(tmp_path / "resume.npz"))
    assert r.returncode == 0, r.stderr[-2000:]
    f = _run_mega_child("full", str(tmp_path / "unused"),
                        str(tmp_path / "full.npz"))
    assert f.returncode == 0, f.stderr[-2000:]

    resumed = np.load(tmp_path / "resume.npz")
    full = np.load(tmp_path / "full.npz")
    assert int(resumed["step"]) == int(full["step"]) == 30
    for key in full.files:
        np.testing.assert_array_equal(
            resumed[key], full[key], err_msg=key,
        )


# -- serving: the same signal becomes the graceful drain ----------------


def _post(base, payload, path="/predict", timeout=60):
    req = urllib.request.Request(base + path,
                                 data=json.dumps(payload).encode())
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code


@pytest.mark.chaos
def test_chaos_sigterm_serving_drain_zero_5xx(tmp_path):
    """ModelServer + ServingRouter under the preemption signal:
    SIGTERM one backend mid-load. Its in-flight requests finish, new
    work sheds with 503 and the router retries it onto the survivor
    — the client sees zero 5xx — and the drained victim exits 0."""
    from deeplearning4j_tpu.serving.router import ServingRouter

    script = os.path.join(REPO_ROOT, "scripts", "bench_serving.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")

    def spawn():
        p = subprocess.Popen(
            [sys.executable, script, "--serve", "--tenants", "1",
             "--preemption-drain"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=env,
        )
        port = int(json.loads(p.stdout.readline())["port"])
        return p, port

    p1, port1 = spawn()
    p2, port2 = spawn()
    r = ServingRouter([f"127.0.0.1:{port1}", f"127.0.0.1:{port2}"],
                      health_interval=0.05).start()
    base = f"http://127.0.0.1:{r.port}"
    rng = np.random.RandomState(CHAOS_SEED)
    feats = rng.rand(1, 32).astype(np.float32).tolist()
    results = []
    lock = threading.Lock()

    def client():
        for _ in range(10):
            code = _post(base, {"model": "m0", "features": feats})
            with lock:
                results.append(code)

    threads = [threading.Thread(target=client) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        os.kill(p1.pid, signal.SIGTERM)  # the preemption notice
        rc1 = p1.wait(timeout=60)        # drained, then exited
        for t in threads:
            t.join(timeout=120)
        assert rc1 == 0, f"victim exited {rc1}, wanted drained 0"
        assert len(results) == 30
        bad = [c for c in results if c >= 500]
        assert not bad, f"{len(bad)} 5xx responses across the drain"
        assert results == [200] * 30, "requests lost across the drain"
        assert r.ready()  # survivor keeps the fleet green
    finally:
        r.stop()
        for p in (p1, p2):
            try:
                p.stdin.close()
                p.wait(timeout=10)
            except Exception:
                p.kill()
