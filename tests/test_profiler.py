"""Hardware-truth profiling storms (registered in
``scripts/run_chaos.sh``): the step profiler, the cost-model MFU
accounting, and the crash-dumping flight recorder.

What must hold:

- the flight-recorder ring is bounded and lock-free safe: concurrent
  writers never tear it, ``tail()`` is always a seq-ordered
  subsequence, dumps are atomic JSONL (temp + ``os.replace``) with a
  header line;
- the ring dumps at the moments that matter — a divergence-guard
  trip, an unhandled fit exception — and on a REAL SIGTERM the dump
  rides the emergency-checkpoint manifest as a CRC-verified artifact
  whose last step record matches the resume step (subprocess storm);
- cost models are deterministic per shape/kind key and cached
  build-once (failures cached as None);
- the step decomposition sums to the measured wall
  (input + host + dispatch + device == wall under a fake clock) and
  the roofline classification follows the stated peaks;
- ``GET /debugz`` on both HTTP servers is a bounded, read-only JSON
  envelope;
- installing the profiler + recorder is trajectory-neutral: params
  and updater state stay BITWISE identical on both engines.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import conftest

from test_resilience import (
    assert_updater_state_match,
    batches as mk_batches,
    simple_net,
)

from deeplearning4j_tpu.datasets.api import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.observability import flightrec, profiler
from deeplearning4j_tpu.observability.flightrec import FlightRecorder
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability.profiler import (
    CostModel,
    CostModelCache,
    StepProfiler,
)
from deeplearning4j_tpu.parallel import DistributedTrainer
from deeplearning4j_tpu.resilience import (
    EXIT_PREEMPTED,
    CheckpointManager,
    DivergenceGuard,
)

CHAOS_SEED = int(os.environ.get("DL4J_TPU_CHAOS_SEED", "1337"))
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_globals():
    """Every test starts with no process-global recorder/profiler and
    leaves whatever was installed before it restored."""
    prev_rec = flightrec.set_flight_recorder(None)
    prev_prof = profiler.set_active_profiler(None)
    yield
    flightrec.set_flight_recorder(prev_rec)
    profiler.set_active_profiler(prev_prof)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


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


def _poisoned(ds):
    bad = ds.features.copy()
    bad[0, 0] = np.nan
    return DataSet(features=bad, labels=ds.labels)


# -- flight recorder: ring mechanics ------------------------------------


class TestFlightRecorderRing:
    def test_ring_bounded_and_seq_ordered(self):
        rec = FlightRecorder(capacity=8)
        for i in range(50):
            rec.record(step=i, loss=float(i))
        tail = rec.tail()
        assert len(tail) == 8  # bounded, not 50
        assert [r["step"] for r in tail] == list(range(42, 50))
        assert [r["seq"] for r in tail] == sorted(
            r["seq"] for r in tail)
        assert rec.last_step() == 49
        # events interleave in arrival order and count toward capacity
        rec.event("compile", key="step:8x4")
        assert rec.tail()[-1]["event"] == "compile"
        assert len(rec.tail()) == 8

    def test_last_step_skips_events(self):
        rec = FlightRecorder(capacity=16)
        assert rec.last_step() is None
        rec.event("guard_trip", step=99)  # an event, not a step
        assert rec.last_step() is None
        rec.record(step=7)
        rec.event("quarantine", offset=3)
        assert rec.last_step() == 7

    def test_disabled_recorder_is_inert(self):
        rec = FlightRecorder(capacity=4, enabled=False)
        rec.record(step=1)
        rec.event("compile")
        assert rec.tail() == []

    def test_ring_thread_safety_under_concurrent_writers(self):
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=64, registry=reg)
        n_threads, per = 6, 400
        errors = []

        def writer(tid):
            try:
                for i in range(per):
                    if i % 5 == 0:
                        rec.event("compile", tid=tid, i=i)
                    else:
                        rec.record(step=i, tid=tid)
            except Exception as e:  # pragma: no cover - must not fire
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        total = reg.counter("flightrec_records_total").value
        assert total == n_threads * per
        tail = rec.tail()
        assert len(tail) <= 64
        seqs = [r["seq"] for r in tail]
        assert seqs == sorted(seqs)
        assert all(r.get("type") in ("step", "event") for r in tail)

    def test_concurrent_reads_during_writes(self):
        rec = FlightRecorder(capacity=32)
        stop = threading.Event()
        errors = []

        def reader():
            try:
                while not stop.is_set():
                    for r in rec.tail(10):
                        assert isinstance(r, dict)
                    rec.last_step()
            except Exception as e:  # pragma: no cover
                errors.append(e)

        t = threading.Thread(target=reader)
        t.start()
        for i in range(5000):
            rec.record(step=i)
        stop.set()
        t.join()
        assert not errors


# -- flight recorder: dumps ---------------------------------------------


class TestFlightRecorderDumps:
    def test_dump_is_atomic_parseable_jsonl(self, tmp_path):
        import jax.numpy as jnp

        rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
        rec.record(step=1, loss=float("nan"),
                   device_val=jnp.float32(2.5))
        rec.record(step=2, loss=0.25, note={"k": np.float64(1.5)})
        rec.event("guard_trip", step=2)
        path = rec.dump(reason="on_demand")
        docs = [json.loads(line)
                for line in open(path).read().splitlines()]
        header, body = docs[0], docs[1:]
        assert header["type"] == "header"
        assert header["reason"] == "on_demand"
        assert header["records"] == 3
        assert header["last_step"] == 2
        assert body[0]["loss"] is None          # NaN -> legal JSON
        assert body[0]["device_val"] == 2.5     # device scalar coerced
        assert body[1]["note"] == {"k": 1.5}
        assert body[2]["event"] == "guard_trip"
        # atomic: no temp litter next to the dump
        assert not list(tmp_path.glob(".flightrec-*"))

    def test_dump_metrics_and_bytes_header(self, tmp_path):
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                             registry=reg)
        assert reg.gauge("flightrec_last_dump_step").value == -1
        rec.record(step=11)
        data = rec.dump_bytes(reason="preemption")
        header = json.loads(data.decode().splitlines()[0])
        assert header["reason"] == "preemption"
        assert header["pid"] == os.getpid()
        fam = reg.counter("flightrec_dumps_total")
        assert fam.labels("preemption").value == 1
        assert reg.gauge("flightrec_last_dump_step").value == 11

    def test_dump_on_crash_none_safe(self):
        # no recorder installed: the one-liner seams must be no-ops
        assert flightrec.dump_on_crash("guard_trip") is None
        flightrec.record_event("compile")  # does not raise

    @pytest.mark.chaos
    def test_chaos_guard_trip_dumps_ring(self, tmp_path):
        """A divergence-guard trip is a crash moment: the ring must
        land on disk with the guard_trip event recorded, and the
        training run must keep going (skip policy)."""
        rng = np.random.RandomState(CHAOS_SEED)
        data = mk_batches(rng, n_batches=3)
        rec = FlightRecorder(capacity=32, dump_dir=str(tmp_path))
        flightrec.set_flight_recorder(rec)
        net = simple_net()
        guard = DivergenceGuard(policy="skip")
        net.set_divergence_guard(guard)
        net.fit_minibatch(data[0])
        net.fit_minibatch(_poisoned(data[1]))
        assert guard.skipped_steps == 1
        dumps = list(tmp_path.glob("flightrec-guard_trip-*.jsonl"))
        assert len(dumps) == 1
        docs = [json.loads(line)
                for line in dumps[0].read_text().splitlines()]
        trips = [d for d in docs if d.get("event") == "guard_trip"]
        assert trips and trips[-1]["policy"] == "skip"
        net.fit_minibatch(data[2])  # training continues after the dump

    @pytest.mark.chaos
    def test_chaos_unhandled_fit_exception_dumps_ring(self, tmp_path):
        """An unhandled exception inside the fit loop dumps the ring
        (reason=fit_exception) and still propagates."""
        rng = np.random.RandomState(CHAOS_SEED + 1)
        data = mk_batches(rng, n_batches=6)
        rec = FlightRecorder(capacity=32, dump_dir=str(tmp_path))
        flightrec.set_flight_recorder(rec)

        class Boom:
            def iteration_done(self, model, it):
                if it == 2:
                    raise RuntimeError("listener exploded")

        net = simple_net()
        net.listeners.append(Boom())
        with pytest.raises(RuntimeError, match="listener exploded"):
            net.fit(ListDataSetIterator(data), epochs=1)
        dumps = list(tmp_path.glob("flightrec-fit_exception-*.jsonl"))
        assert len(dumps) == 1


# -- cost models --------------------------------------------------------


class TestCostModel:
    def test_achieved_and_roofline_math(self):
        cm = CostModel(key="k", flops=2e9, bytes_accessed=1e6)
        ach = cm.achieved(0.01, peak=1e12)
        assert ach["flops_per_sec"] == pytest.approx(2e11)
        assert ach["bytes_per_sec"] == pytest.approx(1e8)
        assert ach["mfu"] == pytest.approx(0.2)
        assert cm.achieved(0.01, peak=None)["mfu"] is None
        assert cm.arithmetic_intensity == pytest.approx(2000.0)
        # balance = peak/peak_bw = 10 flops/byte; intensity 2000 -> compute
        assert cm.roofline_class(1e12, 1e11) == profiler.ROOFLINE_COMPUTE
        # raise the machine balance above the intensity -> memory
        assert cm.roofline_class(1e15, 1e11) == profiler.ROOFLINE_MEMORY
        assert cm.roofline_class(None, 1e11) == profiler.ROOFLINE_UNKNOWN

    def test_peak_env_overrides(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "2.5e12")
        monkeypatch.setenv("DL4J_TPU_PEAK_BYTES_PER_SEC", "8e11")
        assert profiler.peak_flops() == (2.5e12, "env")
        assert profiler.peak_bytes_per_sec() == (8e11, "env")
        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "not-a-number")
        v, src = profiler.peak_flops()
        assert src != "env"  # garbage falls through to the chip table

    def test_train_step_cost_model_deterministic_per_key(self):
        rng = np.random.RandomState(CHAOS_SEED)
        ds8 = mk_batches(rng, n_batches=1, batch=8)[0]
        ds16 = mk_batches(rng, n_batches=1, batch=16)[0]
        m = simple_net()
        m.fit_minibatch(ds8)
        cm_a = profiler.train_step_cost_model(m, ds8)
        cm_b = profiler.train_step_cost_model(m, ds8)
        assert cm_a.key == cm_b.key
        assert cm_a.flops == cm_b.flops > 0
        assert cm_a.bytes_accessed == cm_b.bytes_accessed > 0
        assert "8x4" in cm_a.key  # keyed by the batch geometry
        cm_c = profiler.train_step_cost_model(m, ds16)
        assert cm_c.key != cm_a.key
        assert cm_c.flops > cm_a.flops  # more rows, more work

    def test_cache_builds_once_and_caches_failures(self):
        cache = CostModelCache()
        cm = CostModel(key="k", flops=1.0, bytes_accessed=2.0)
        calls = []

        def build():
            calls.append(1)
            return cm

        assert cache.get_or_build("a", build) is cm
        assert cache.get_or_build("a", build) is cm
        assert len(calls) == 1

        def boom():
            calls.append(1)
            raise RuntimeError("unlowerable")

        assert cache.get_or_build("b", boom) is None
        assert cache.get_or_build("b", boom) is None  # one attempt
        assert len(calls) == 2
        snap = cache.snapshot()
        assert snap["a"]["flops"] == 1.0 and snap["b"] is None


# -- step profiler ------------------------------------------------------


class TestStepProfiler:
    def test_decomposition_sums_to_wall(self):
        clock = FakeClock()
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=8)
        prof = StepProfiler(registry=reg, recorder=rec, clock=clock,
                            peak=1e12, peak_bw=1e11)
        prof.begin_step(7)
        clock.t += 0.010  # a 10ms step
        prof.note_input_wait_ms(2.0)
        prof.note_dispatch_ms(1.0)
        prof.note_device_ms(3.0)
        out = prof.end_step(score=0.5, rows=8,
                            cost=CostModel(key="k", flops=2e9,
                                           bytes_accessed=1e6))
        assert out["wall_ms"] == pytest.approx(10.0)
        parts = (out["input_stall_ms"] + out["host_ms"]
                 + out["dispatch_ms"] + out["device_ms"])
        assert parts == pytest.approx(out["wall_ms"])
        assert out["host_ms"] == pytest.approx(4.0)  # the remainder
        assert out["step"] == 7 and out["loss"] == 0.5
        # MFU = 2e9 / 0.01s / 1e12 peak
        assert out["mfu"] == pytest.approx(0.2)
        assert out["roofline"] == "compute_bound"
        assert reg.gauge("step_mfu").value == pytest.approx(0.2)
        assert reg.gauge("step_flops_per_sec").value == \
            pytest.approx(2e11)
        assert reg.gauge("step_bytes_per_sec").value == \
            pytest.approx(1e8)
        assert reg.gauge("step_roofline_class").value == \
            float(profiler.ROOFLINE_COMPUTE)
        # the record landed in the ring verbatim
        assert rec.last_step() == 7

    def test_input_bound_overrides_roofline_class(self):
        clock = FakeClock()
        prof = StepProfiler(registry=MetricsRegistry(), clock=clock,
                            peak=1e12, peak_bw=1e11,
                            input_bound_frac=0.25)
        prof.begin_step(1)
        clock.t += 0.010
        prof.note_input_wait_ms(6.0)  # 60% of wall: starved
        out = prof.end_step(cost=CostModel(key="k", flops=2e9,
                                           bytes_accessed=1e6))
        assert out["roofline"] == "input_bound"

    def test_disabled_profiler_is_inert(self):
        prof = StepProfiler(registry=MetricsRegistry(), enabled=False)
        prof.begin_step(1)
        prof.note_input_wait_ms(5.0)
        assert prof.end_step(score=1.0) is None

    def test_abandon_step_drops_state(self):
        clock = FakeClock()
        prof = StepProfiler(registry=MetricsRegistry(), clock=clock)
        prof.begin_step(3)
        prof.abandon_step()
        assert prof.end_step() is None  # unpaired end: nothing

    def test_scan_chunk_record_opens_at_its_first_feed_wait(self):
        """On the scan path a slow iterator shows up as
        ``input_stall_ms``, not as ``host_ms``: the chunk's record
        opens where its first ``fit.feed_wait`` opens, and the enqueue
        of the fused program is its ``dispatch_ms``."""
        class Throttled(ListDataSetIterator):
            def next(self):
                time.sleep(0.005)
                return super().next()

        rng = np.random.RandomState(0)
        bs = mk_batches(rng, n_batches=16)
        # the program that stacks a chunk in device memory compiles
        # once a process for these shapes, outside any dispatch span:
        # here, so that the record's host_ms is the steady state's
        simple_net().fit(ListDataSetIterator(bs), epochs=1)
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=16, registry=reg)
        prof = StepProfiler(registry=reg, recorder=rec)
        profiler.set_active_profiler(prof)
        try:
            net = simple_net()
            net.fit(Throttled(bs), epochs=1)
        finally:
            profiler.set_active_profiler(None)
        assert net._jit_multi_step is not None and net._jit_step is None
        (chunk,) = [r for r in rec.tail() if r.get("type") == "step"]
        assert chunk["step"] == 16 and chunk["rows"] == 16 * len(
            bs[0].features)
        assert chunk["input_stall_ms"] >= 16 * 5.0
        assert chunk["dispatch_ms"] > 0  # first call: the compile
        assert chunk["host_ms"] < chunk["input_stall_ms"]
        parts = (chunk["input_stall_ms"] + chunk["host_ms"]
                 + chunk["dispatch_ms"] + chunk["device_ms"])
        assert parts == pytest.approx(chunk["wall_ms"], abs=0.01)
        assert prof.open_span() is None  # nothing left open

    @pytest.mark.chaos
    def test_chaos_profiler_trajectory_neutral_both_engines(self):
        """Installing the profiler + recorder must not perturb the
        trajectory: params AND updater state bitwise on both
        engines."""
        rng = np.random.RandomState(CHAOS_SEED)
        bs = mk_batches(rng, n_batches=6)

        ref = simple_net()
        DistributedTrainer(ref).fit(ListDataSetIterator(bs), epochs=2)
        gref = graph_net()
        gref.fit(ListDataSetIterator(bs), epochs=2)

        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=64, registry=reg)
        flightrec.set_flight_recorder(rec)
        prof = StepProfiler(registry=reg, recorder=rec)
        profiler.set_active_profiler(prof)
        m = simple_net()
        DistributedTrainer(m).fit(ListDataSetIterator(bs), epochs=2)
        g = graph_net()
        g.fit(ListDataSetIterator(bs), epochs=2)
        profiler.set_active_profiler(None)

        conftest.assert_params_match(ref, m)
        assert_updater_state_match(ref, m)
        conftest.assert_params_match(gref, g)
        assert_updater_state_match(gref, g)
        # and the instrumentation actually observed the runs
        assert rec.last_step() == 12
        steps = [r for r in rec.tail() if r.get("type") == "step"]
        # compile events share the ring, so only the freshest step
        # records are retained — there must be some, fully formed
        assert len(steps) >= 12
        assert all("wall_ms" in r for r in steps)
        assert reg.gauge("step_flops_per_sec").value > 0


# -- /debugz ------------------------------------------------------------


def _get_json(base, path, timeout=10):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _serving_net(seed=2):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=3, n_out=6, activation="tanh"))
        .layer(OutputLayer(n_out=2))
        .build()
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    return MultiLayerNetwork(conf).init()


class TestDebugz:
    def test_model_server_debugz_bounded_read_only(self):
        from deeplearning4j_tpu.serving import ModelServer

        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=512, registry=reg)
        flightrec.set_flight_recorder(rec)
        for i in range(3 * flightrec.DEBUG_TAIL_LIMIT):
            rec.record(step=i)
        prof = StepProfiler(registry=reg, recorder=rec)
        profiler.set_active_profiler(prof)

        s = ModelServer(_serving_net(), workers=1).start()
        try:
            base = f"http://127.0.0.1:{s.port}"
            code, doc = _get_json(base, "/debugz")
            assert code == 200
            for key in ("versions", "backend", "config", "models",
                        "metrics", "roofline", "profiler",
                        "flight_recorder"):
                assert key in doc, key
            assert doc["versions"]["jax"]
            assert doc["config"]["port"] == s.port
            # bucket cost models from warmup, keyed name:bucket
            assert isinstance(
                doc["roofline"]["bucket_cost_models"], dict)
            # bounded: the tail never exceeds the debug cap
            tail = doc["flight_recorder"]["tail"]
            assert len(tail) == flightrec.DEBUG_TAIL_LIMIT
            assert doc["flight_recorder"]["last_step"] == \
                3 * flightrec.DEBUG_TAIL_LIMIT - 1
            # read-only: serving /debugz never writes a dump
            assert reg.gauge("flightrec_last_dump_step").value == -1
            code2, doc2 = _get_json(base, "/debugz")
            assert code2 == 200 and set(doc2) == set(doc)
        finally:
            s.stop()

    def test_ui_server_debugz(self):
        from deeplearning4j_tpu.ui.server import UIServer

        rec = FlightRecorder(capacity=16)
        rec.record(step=5)
        flightrec.set_flight_recorder(rec)
        s = UIServer(port=0)
        try:
            base = f"http://127.0.0.1:{s.port}"
            code, doc = _get_json(base, "/debugz")
            assert code == 200
            for key in ("versions", "backend", "config", "sessions",
                        "metrics", "flight_recorder"):
                assert key in doc, key
            assert doc["config"]["port"] == s.port
            assert doc["flight_recorder"]["last_step"] == 5
        finally:
            s.stop()


# -- the real signal: SIGTERM storm with the recorder live --------------

_PROF_CHILD = r"""
import os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.flightrec import (
    FlightRecorder, set_flight_recorder,
)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability.profiler import (
    StepProfiler, set_active_profiler,
)
from deeplearning4j_tpu.parallel import DistributedTrainer
from deeplearning4j_tpu.resilience import (
    CheckpointManager, PreemptionHandler, exit_on_preemption,
)

ckpt_dir = sys.argv[1]

def net():
    conf = (NeuralNetConfiguration.Builder().seed(7)
            .learning_rate(0.05).updater("ADAM").list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3)).build())
    return MultiLayerNetwork(conf).init()

rng = np.random.RandomState(int(os.environ.get(
    "DL4J_TPU_CHAOS_SEED", "1337")))
bs = [DataSet(
    features=rng.randn(8, 4).astype(np.float32),
    labels=np.eye(3)[rng.randint(0, 3, 8)].astype(np.float32),
) for _ in range(30)]

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

reg = MetricsRegistry()
rec = FlightRecorder(capacity=256, registry=reg, dump_dir=ckpt_dir)
set_flight_recorder(rec)
set_active_profiler(StepProfiler(registry=reg, recorder=rec))

m = net()
tr = DistributedTrainer(m)
mgr = CheckpointManager(ckpt_dir)

class Progress:
    def iteration_done(self, model, it):
        print(f"step {it}", flush=True)
m.listeners.append(Progress())
PreemptionHandler(manager=mgr).install()
with exit_on_preemption():
    tr.fit(Paced(bs), epochs=1, prefetch=2)
"""


@pytest.mark.chaos
def test_chaos_sigterm_flightrec_artifact_rides_manifest(tmp_path):
    """The acceptance storm: SIGTERM a training subprocess with the
    profiler + flight recorder live. The process must exit 75 with an
    emergency checkpoint whose manifest carries a CRC-verified
    ``flightrec.jsonl`` artifact, and the artifact's last step record
    must match the step a fresh process resumes from."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt, exist_ok=True)
    p = subprocess.Popen(
        [sys.executable, "-c", _PROF_CHILD, ckpt],
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
    info = mgr.available()[-1]
    step = info.step
    assert step >= 3 and step == mgr.latest_step()

    # the ring rode the manifest, CRC-verified on read
    assert "flightrec.jsonl" in info.artifacts
    data = mgr.load_artifact(info, "flightrec.jsonl")
    assert data is not None, "artifact failed CRC verification"
    docs = [json.loads(line) for line in data.decode().splitlines()]
    header = docs[0]
    assert header["type"] == "header"
    assert header["reason"] == "preemption"
    assert header["last_step"] == step
    step_recs = [d for d in docs[1:] if d.get("type") == "step"]
    assert step_recs, "no step records in the dumped ring"
    assert step_recs[-1]["step"] == step
    assert "wall_ms" in step_recs[-1]  # the profiler wrote them
    events = [d.get("event") for d in docs[1:]
              if d.get("type") == "event"]
    assert "preemption_notice" in events

    # ... and that step IS the resume step
    survivor = simple_net()
    assert DistributedTrainer(survivor).resume(mgr) == step

    # the CRC gate is real: corrupt one byte, the loader refuses
    art_path = os.path.join(ckpt,
                            info.artifacts["flightrec.jsonl"]["file"])
    with open(art_path, "r+b") as f:
        f.seek(10)
        b = f.read(1)
        f.seek(10)
        f.write(bytes([b[0] ^ 0xFF]))
    assert mgr.load_artifact(info, "flightrec.jsonl") is None
