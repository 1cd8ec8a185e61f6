"""Multi-tenant serving fleet tests (tier-1, CPU-only): the model
registry (named tenants, per-model quotas/deadlines), LRU
device-memory weight paging (evict cold -> host, fault back in
bitwise-identical with ZERO XLA compiles), tenant isolation under
overload (one tenant at 10x quota sheds 503s while its neighbor's
p99 stays sane), the adaptive Retry-After, and the fleet router
(rendezvous placement, least-loaded fallback, health-aware failover
with zero request loss — including the SIGKILL-a-backend chaos
storm registered in scripts/run_chaos.sh)."""

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

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import (
    ModelRegistry,
    ModelServer,
    ModelVersion,
    ServingRouter,
    jit_cache_size,
    page_in_model,
    page_out_model,
)
from deeplearning4j_tpu.serving.server import (
    RETRY_AFTER_MAX,
    RETRY_AFTER_MIN,
)

CHAOS_SEED = int(os.environ.get("DL4J_TPU_CHAOS_SEED", "1337"))


def _post(base, payload, path="/predict", timeout=30):
    req = urllib.request.Request(base + path,
                                 data=json.dumps(payload).encode())
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(base, path, timeout=10):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _mlp(seed=2, n_in=3, n_out=2):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=n_in, n_out=4, activation="tanh"))
        .layer(OutputLayer(n_out=n_out))
        .build()
    )
    return MultiLayerNetwork(conf).init()


class SleepModel:
    """Stub with a fixed service time; output = x * k."""

    def __init__(self, delay=0.0, k=2.0):
        self.delay = delay
        self.k = k
        self.calls = 0

    def output(self, feats):
        self.calls += 1
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(feats, np.float32) * self.k


class _Weighted:
    """Minimal pageable model: a params pytree of jax arrays."""

    def __init__(self, n=8):
        import jax.numpy as jnp

        self.params = {"w": jnp.arange(n * n, dtype=jnp.float32)
                       .reshape(n, n)}

    def output(self, feats):
        return np.asarray(feats, np.float32)


def _version(model, v=1):
    return ModelVersion(model, v, "test")


# -- registry + paging primitives ---------------------------------------


class TestModelRegistry:
    def test_named_lookup_and_default(self):
        reg = ModelRegistry()
        a = reg.add("a", _version(SleepModel()))
        reg.add("b", _version(SleepModel()))
        assert reg.entry() is a            # first added is default
        assert reg.entry("b").name == "b"
        with pytest.raises(KeyError):
            reg.entry("nope")
        with pytest.raises(ValueError):
            reg.add("a", _version(SleepModel()))

    def test_quota_admission_bound(self):
        reg = ModelRegistry()
        e = reg.add("a", _version(SleepModel()), quota=2)
        assert e.admit() and e.admit()
        assert not e.admit()          # at quota: shed
        e.exit_admission()
        assert e.admit()              # slot freed
        free = reg.add("b", _version(SleepModel()))  # quota=None
        assert all(free.admit() for _ in range(64))

    def test_lru_evicts_coldest_unpinned(self):
        t = [0.0]
        reg = ModelRegistry(max_device_models=2,
                            clock=lambda: t[0])
        entries = {}
        for name in ("a", "b", "c"):
            t[0] += 1.0
            entries[name] = reg.add(name, _version(_Weighted()))
        # touch order: a (oldest use), then b, then c pushes over
        for name in ("a", "b", "c"):
            t[0] += 1.0
            reg.touch(entries[name])
            reg.release(entries[name])
        reg.enforce_budget()
        assert entries["a"].resident == "host"   # coldest
        assert entries["b"].resident == "device"
        assert entries["c"].resident == "device"

    def test_pinned_and_executing_never_evicted(self):
        t = [0.0]
        reg = ModelRegistry(max_device_models=1,
                            clock=lambda: t[0])
        a = reg.add("a", _version(_Weighted()), pinned=True)
        b = reg.add("b", _version(_Weighted()))
        reg.enforce_budget()
        assert a.resident == "device"      # pinned survives
        assert b.resident == "host"        # unpinned idle pays
        # an executing entry is never a victim, even over budget
        t[0] += 1.0
        reg.touch(b)                       # faults b in; budget=1 but
        assert b.resident == "device"      # a pinned + b executing ->
        assert a.resident == "device"      # nothing evictable
        reg.release(b)

    def test_max_device_bytes_budget(self):
        t = [0.0]
        w = _Weighted(8)                   # 8*8*4 = 256 bytes each
        reg = ModelRegistry(max_device_bytes=300,
                            clock=lambda: t[0])
        a = reg.add("a", _version(w))
        t[0] += 1.0
        b = reg.add("b", _version(_Weighted(8)))
        reg.enforce_budget()               # 512 > 300: evict coldest
        assert a.resident == "host" and b.resident == "device"

    def test_fault_in_counts_and_measures(self):
        from deeplearning4j_tpu.observability.metrics import (
            MetricsRegistry,
        )

        mreg = MetricsRegistry()
        reg = ModelRegistry(max_device_models=1,
                            metrics_registry=mreg)
        a = reg.add("a", _version(_Weighted()))
        b = reg.add("b", _version(_Weighted()))
        reg.enforce_budget()
        assert mreg.counter("weight_evict_total").value == 1
        faulted = b if b.resident == "host" else a
        ms = reg.touch(faulted)
        reg.release(faulted)
        assert ms is not None and ms >= 0.0
        assert mreg.counter("weight_pagein_total").value == 1
        assert mreg.summary("weight_pagein_ms").snapshot()["count"] == 1
        # resident entry: touch is a no-op fault-wise
        assert reg.touch(faulted) is None
        reg.release(faulted)

    def test_page_roundtrip_is_bitwise_and_compile_free(self):
        net = _mlp(seed=11)
        x = np.random.RandomState(0).rand(4, 3).astype(np.float32)
        ref = np.asarray(net.output(x))
        jit0 = jit_cache_size(net)
        moved_out = page_out_model(net)
        assert moved_out > 0
        # paged out: params live on host as numpy
        leaves = [v for d in net.params.values() for v in d.values()]
        assert all(isinstance(a, np.ndarray) for a in leaves)
        moved_in = page_in_model(net)
        assert moved_in == moved_out
        out = np.asarray(net.output(x))
        assert out.tobytes() == ref.tobytes()
        assert jit_cache_size(net) == jit0  # transfer, not compile


# -- multi-tenant server ------------------------------------------------


class TestMultiTenantServer:
    def test_routes_by_model_name_bitwise(self):
        nets = {"a": _mlp(seed=1), "b": _mlp(seed=2)}
        refs = {}
        x = np.random.RandomState(3).rand(2, 3).astype(np.float32)
        for name, net in nets.items():
            refs[name] = np.asarray(net.output(x))
        s = ModelServer(models=dict(nets), workers=2).start()
        base = f"http://127.0.0.1:{s.port}"
        try:
            for name in ("a", "b"):
                code, body, _ = _post(base, {
                    "model": name, "features": x.tolist(),
                })
                assert code == 200
                assert body["model"] == name
                got = np.asarray(body["output"], np.float32)
                assert got.tobytes() == refs[name].tobytes()
            # default tenant (first registered) answers bare posts
            code, body, _ = _post(base, {"features": x.tolist()})
            assert code == 200 and body["model"] == "a"
            code, body = _get(base, "/models")
            assert set(body["models"]) == {"a", "b"}
            assert body["default"] == "a"
        finally:
            s.stop(drain_timeout=2)

    def test_unknown_model_404_envelope(self):
        s = ModelServer(models={"a": SleepModel()}, workers=1).start()
        base = f"http://127.0.0.1:{s.port}"
        try:
            code, body, _ = _post(base, {
                "model": "ghost", "features": [[1.0]],
            })
            assert code == 404
            assert body["error"]["status"] == "model_not_found"
            assert body["error"]["models"] == ["a"]
            code, body, _ = s.submit(np.ones((1, 1), np.float32),
                                     model="ghost")
            assert code == 404
        finally:
            s.stop(drain_timeout=2)

    def test_per_model_metrics_readable_from_one_scrape(self):
        s = ModelServer(models={"a": SleepModel(),
                                "b": SleepModel()},
                        workers=2).start()
        base = f"http://127.0.0.1:{s.port}"
        try:
            for _ in range(3):
                assert _post(base, {"model": "a",
                                    "features": [[1.0]]})[0] == 200
            assert _post(base, {"model": "b",
                                "features": [[1.0]]})[0] == 200
            code, snap = _get(base, "/metrics")
            assert snap["models"]["a"]["model_predictions_total"] == 3
            assert snap["models"]["b"]["model_predictions_total"] == 1
            assert snap["models"]["a"]["latency_ms"]["count"] == 3
            assert "p99" in snap["models"]["a"]["latency_ms"]
            # Prometheus exposition carries the model label
            req = urllib.request.urlopen(
                base + "/metrics?format=prometheus", timeout=10
            )
            text = req.read().decode()
            assert 'model_requests_total{model="a"} 3' in text
            assert 'model_requests_total{model="b"} 1' in text
        finally:
            s.stop(drain_timeout=2)

    def test_single_model_backcompat_shape(self):
        s = ModelServer(SleepModel(), workers=1).start()
        base = f"http://127.0.0.1:{s.port}"
        try:
            code, body, _ = _post(base, {"features": [[2.0, 2.0]]})
            assert code == 200
            assert "model" not in body       # legacy response shape
            assert body["output"] == [[4.0, 4.0]]
            assert s.model_version == 1
        finally:
            s.stop(drain_timeout=2)

    def test_per_tenant_reload(self, tmp_path):
        from deeplearning4j_tpu.util.model_serializer import (
            write_model,
        )

        net_v1, net_v2 = _mlp(seed=5), _mlp(seed=6)
        p = str(tmp_path / "tenant-b.zip")
        write_model(net_v1, p)
        s = ModelServer(models={"a": _mlp(seed=4), "b": p},
                        workers=1).start()
        base = f"http://127.0.0.1:{s.port}"
        x = np.ones((1, 3), np.float32)
        try:
            write_model(net_v2, p)
            code, body, _ = _post(base, {"model": "b"},
                                  path="/admin/reload")
            assert code == 200 and body["version"] == 2
            assert body["name"] == "b"
            # tenant a untouched by b's reload
            assert s.model_registry.entry("a").current.version == 1
            code, body, _ = _post(base, {
                "model": "b", "features": x.tolist(),
            })
            ref = np.asarray(net_v2.output(x), np.float32)
            got = np.asarray(body["output"], np.float32)
            assert got.tobytes() == ref.tobytes()
        finally:
            s.stop(drain_timeout=2)


# -- LRU paging through the server --------------------------------------


class TestServerWeightPaging:
    def test_evict_fault_in_bitwise_zero_compiles(self):
        nets = {f"m{i}": _mlp(seed=20 + i) for i in range(3)}
        x = np.random.RandomState(7).rand(2, 3).astype(np.float32)
        s = ModelServer(models=dict(nets), workers=2,
                        max_device_models=2).start()
        base = f"http://127.0.0.1:{s.port}"
        try:
            refs = {}
            for name in nets:  # serve all three once
                code, body, _ = _post(base, {
                    "model": name, "features": x.tolist(),
                })
                assert code == 200
                refs[name] = body["output"]
            snap = s.metrics_snapshot()
            paged = [n for n, m in snap["paging"]["models"].items()
                     if m["resident"] == "host"]
            assert paged, "3 tenants under a budget of 2 must page"
            cold = paged[0]
            compiles0 = s.metrics.get("xla_compiles_total")
            jit0 = jit_cache_size(nets[cold])
            pageins0 = snap["paging"]["weight_pagein_total"]
            # fault the cold tenant back in: transfer, not compile
            code, body, _ = _post(base, {
                "model": cold, "features": x.tolist(),
            })
            assert code == 200
            assert body["output"] == refs[cold]  # bitwise via json
            snap = s.metrics_snapshot()
            assert snap["paging"]["models"][cold]["resident"] == \
                "device"
            assert snap["paging"]["weight_pagein_total"] > pageins0
            assert snap["paging"]["weight_evict_total"] >= 1
            assert s.metrics.get("xla_compiles_total") == compiles0
            assert jit_cache_size(nets[cold]) == jit0
            assert s.metrics.get("post_warmup_compiles_total") == 0
        finally:
            s.stop(drain_timeout=2)

    def test_pinned_tenant_never_pages(self):
        s = ModelServer(
            models={"hot": {"model": _mlp(seed=30), "pinned": True},
                    "cold": _mlp(seed=31)},
            workers=1, max_device_models=1,
        ).start()
        x = np.ones((1, 3), np.float32)
        try:
            # startup budget enforcement paged the unpinned tenant out
            snap = s.metrics_snapshot()["paging"]["models"]
            assert snap["hot"]["resident"] == "device"
            assert snap["hot"]["pinned"] is True
            assert snap["cold"]["resident"] == "host"
            for name in ("cold", "hot", "cold", "hot"):
                assert s.submit(x, model=name)[0] == 200
            snap = s.metrics_snapshot()["paging"]["models"]
            assert snap["hot"]["resident"] == "device"  # never left
        finally:
            s.stop(drain_timeout=2)


# -- tenant isolation under overload ------------------------------------


class TestTenantIsolation:
    def test_overloaded_tenant_sheds_neighbor_unharmed(self):
        """Tenant A floods at ~10x its quota; every shed is charged
        to A's own bound (503 tenant_quota) and B — a polite
        single-stream client — sees zero errors and a bounded p99."""
        rng = np.random.RandomState(CHAOS_SEED)
        s = ModelServer(
            models={"a": {"model": SleepModel(delay=0.01),
                          "quota": 3},
                    "b": SleepModel(delay=0.001)},
            workers=8, queue_depth=64, micro_batch=False,
        ).start()
        xa = rng.rand(1, 4).astype(np.float32)
        xb = rng.rand(1, 4).astype(np.float32)
        stop_flood = threading.Event()
        a_codes = []

        def flood():
            while not stop_flood.is_set():
                code = s.submit(xa, model="a")[0]
                a_codes.append(code)
                if code == 503:   # pace the spin: a real client backs
                    time.sleep(0.005)  # off on Retry-After

        floods = [threading.Thread(target=flood) for _ in range(30)]
        for t in floods:
            t.start()
        b_lat, b_codes = [], []
        try:
            deadline = time.monotonic() + 20
            while len(b_codes) < 40 and time.monotonic() < deadline:
                t0 = time.perf_counter()
                code, _, _ = s.submit(xb, model="b")
                b_lat.append(time.perf_counter() - t0)
                b_codes.append(code)
        finally:
            stop_flood.set()
            for t in floods:
                t.join(timeout=10)
            snap = s.metrics_snapshot()
            s.stop(drain_timeout=2)
        assert b_codes == [200] * len(b_codes)  # zero shed/error on B
        assert 503 in a_codes                   # A actually overloaded
        assert snap["quota_rejected_total"] > 0
        assert snap["models"]["a"]["model_shed_total"] > 0
        assert snap["models"]["b"].get("model_shed_total", 0) == 0
        b_lat.sort()
        p99 = b_lat[min(len(b_lat) - 1, int(0.99 * len(b_lat)))]
        # B's service time is ~1 ms; even a GIL-shared 1-core CI box
        # must keep its p99 well under a second when A is quota-boxed
        assert p99 < 1.0, f"neighbor p99 degraded to {p99:.3f}s"


# -- adaptive Retry-After -----------------------------------------------


class TestAdaptiveRetryAfter:
    def test_knob_is_the_cap_until_drain_history_exists(self):
        s = ModelServer(SleepModel(), workers=1, retry_after=3.0)
        s2 = ModelServer(SleepModel(), workers=1, retry_after=9.0)
        try:
            assert s.retry_after_value() == 3.0  # no completions yet
            assert s2.retry_after_value() == RETRY_AFTER_MAX
        finally:
            s._httpd.server_close()
            s2._httpd.server_close()

    def test_value_tracks_queue_depth_over_drain_rate(self):
        s = ModelServer(SleepModel(), workers=1, queue_depth=32,
                        retry_after=5.0)
        try:
            # synthetic drain history: 100 completions/s
            for i in range(21):
                s.metrics.note_completion(i * 0.01)
            assert s.retry_after_value() == RETRY_AFTER_MIN  # empty q
            for _ in range(10):
                s._queue.put_nowait(object())  # unstarted: no drain
            est = s.retry_after_value()
            assert est == pytest.approx(10 / 100.0)  # depth / rate
            # the knob stays an upper bound however deep the queue is
            for _ in range(20):
                s._queue.put_nowait(object())
            s.retry_after = 0.2
            assert s.retry_after_value() == pytest.approx(0.2)
        finally:
            s._httpd.server_close()

    def test_shed_envelope_carries_adaptive_value(self):
        gate = threading.Event()

        class Gated:
            def output(self, feats):
                gate.wait(10)
                return np.asarray(feats, np.float32)

        s = ModelServer(Gated(), workers=1, queue_depth=0,
                        retry_after=2.5, micro_batch=False).start()
        x = np.ones((1, 2), np.float32)
        try:
            hold = threading.Thread(
                target=lambda: s.submit(x)
            )
            hold.start()
            deadline = time.monotonic() + 5
            while (s.metrics.inflight < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            code, body, headers = s.submit(x)
            assert code == 503
            ra = body["error"]["retry_after"]
            assert RETRY_AFTER_MIN <= ra <= 2.5
            assert int(headers["Retry-After"]) >= 1
        finally:
            gate.set()
            s.stop(drain_timeout=2)


# -- router -------------------------------------------------------------


def _stub_server(delay=0.0, **kw):
    kw.setdefault("workers", 2)
    return ModelServer(SleepModel(delay=delay), **kw).start()


class TestRouter:
    def test_rendezvous_is_deterministic_and_spreads(self):
        r = ServingRouter(["127.0.0.1:1", "127.0.0.1:2",
                           "127.0.0.1:3"])
        try:
            orders = {m: [b.address for b in r.candidates(m)]
                      for m in ("m0", "m1", "m2", "m3", "m4", "m5")}
            # stable: same model, same order, every time
            for m, order in orders.items():
                assert [b.address for b in r.candidates(m)] == order
                assert len(order) == 3
            # spreads: >1 distinct primary across a handful of models
            assert len({o[0] for o in orders.values()}) > 1
        finally:
            r.stop()

    def test_unhealthy_backends_drop_out(self):
        r = ServingRouter(["127.0.0.1:1", "127.0.0.1:2"])
        try:
            r.backends[0].healthy = False
            for m in ("a", "b", "c"):
                assert [b.address for b in r.candidates(m)] == \
                    ["127.0.0.1:2"]
            r.backends[1].healthy = False
            assert r.candidates("a") == []
            assert not r.ready()
        finally:
            r.stop()

    def test_least_loaded_fallback(self):
        r = ServingRouter(["127.0.0.1:1", "127.0.0.1:2"],
                          spread_after=4)
        try:
            primary = r.candidates("model-x")[0]
            other = [b for b in r.backends if b is not primary][0]
            primary.outstanding = 10       # owner is slammed
            assert r.candidates("model-x")[0] is other
            primary.outstanding = 2        # small gap: hash wins
            assert r.candidates("model-x")[0] is primary
        finally:
            r.stop()

    def test_health_poll_jitter_is_seeded(self):
        """N routers must not synchronize their /readyz probes: each
        jitters its poll interval from a seeded RNG — deterministic
        per seed, decorrelated across seeds, inside the ±jitter
        band."""
        a = ServingRouter(["127.0.0.1:1"], seed=CHAOS_SEED,
                          health_interval=0.25, health_jitter=0.2)
        b = ServingRouter(["127.0.0.1:1"], seed=CHAOS_SEED,
                          health_interval=0.25, health_jitter=0.2)
        c = ServingRouter(["127.0.0.1:1"], seed=CHAOS_SEED + 1,
                          health_interval=0.25, health_jitter=0.2)
        flat = ServingRouter(["127.0.0.1:1"], health_jitter=0.0)
        try:
            seq_a = [a._next_interval() for _ in range(8)]
            seq_b = [b._next_interval() for _ in range(8)]
            seq_c = [c._next_interval() for _ in range(8)]
            assert seq_a == seq_b          # same seed replays
            assert seq_a != seq_c          # different seed differs
            assert len(set(seq_a)) > 1     # actually jitters
            for v in seq_a:
                assert 0.25 * 0.8 <= v <= 0.25 * 1.2
            assert flat._next_interval() == flat.health_interval
        finally:
            for r in (a, b, c, flat):
                r.stop()
        with pytest.raises(ValueError):
            ServingRouter(["127.0.0.1:1"], health_jitter=1.5)

    @pytest.mark.chaos
    def test_readyz_probe_timeout_marks_unhealthy(self):
        """A backend that ACCEPTS the connection but never answers
        /readyz (wedged process) is exactly as dead as one refusing
        connections: the poll times out and the backend drops out of
        candidate order immediately."""
        import socket

        wedge = socket.socket()
        wedge.bind(("127.0.0.1", 0))
        wedge.listen(8)  # accepts, never reads or answers
        port = wedge.getsockname()[1]
        r = ServingRouter([f"127.0.0.1:{port}"], probe_timeout=0.2)
        try:
            t0 = time.monotonic()
            assert r.check_health() == 0
            assert time.monotonic() - t0 < 2.0  # timed out, not hung
            assert not r.backends[0].healthy
            assert r.candidates("m") == []
        finally:
            r.stop()
            wedge.close()

    def test_forwards_and_relays_envelopes(self):
        s = _stub_server()
        r = ServingRouter([f"127.0.0.1:{s.port}"]).start()
        base = f"http://127.0.0.1:{r.port}"
        try:
            code, body, _ = _post(base, {"features": [[3.0]]})
            assert code == 200 and body["output"] == [[6.0]]
            code, body, _ = _post(base, {"nope": 1})
            assert code == 400    # backend's envelope relays verbatim
            assert body["error"]["status"] == "bad_request"
            code, body = _get(base, "/readyz")
            assert code == 200
            snap = r.metrics_snapshot()
            assert snap["router_requests_total"] == 2
            assert snap["backends"][0]["forwarded"] == 2
        finally:
            r.stop()
            s.stop(drain_timeout=2)

    def test_failover_zero_loss_when_backend_dies_midload(self):
        """Kill one of two backends under load: every request still
        answers 200 — the router retries connection failures on the
        survivor."""
        s1 = _stub_server(delay=0.002)
        s2 = _stub_server(delay=0.002)
        r = ServingRouter(
            [f"127.0.0.1:{s1.port}", f"127.0.0.1:{s2.port}"],
            health_interval=0.05,
        ).start()
        base = f"http://127.0.0.1:{r.port}"
        results = []
        lock = threading.Lock()

        def client(tid):
            for i in range(15):
                code, _, _ = _post(base, {
                    "model": None,
                    "features": [[float(tid), float(i)]],
                }, timeout=30)
                with lock:
                    results.append(code)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        s1.stop(drain_timeout=0.2)     # dies mid-load
        for t in threads:
            t.join(timeout=30)
        try:
            assert len(results) == 60
            assert results == [200] * 60, (
                f"lost {sum(1 for c in results if c != 200)} requests"
            )
            assert r.ready()           # survivor keeps /readyz green
        finally:
            r.stop()
            s2.stop(drain_timeout=2)


# -- fleet chaos storm (registered in scripts/run_chaos.sh) -------------


@pytest.mark.chaos
def test_chaos_fleet_sigkill_backend_recovers_warm(tmp_path):
    """SIGKILL one backend process mid-load: zero request loss
    (router retries onto the survivor), then the backend restarts
    WARM from the shared persistent compile cache and the router's
    health poll routes to it again."""
    script = os.path.join(os.path.dirname(__file__), "..",
                          "scripts", "bench_serving.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")

    def spawn():
        p = subprocess.Popen(
            [sys.executable, script, "--serve", "--tenants", "1"],
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
        for _ in range(12):
            code, _, _ = _post(base, {"model": "m0",
                                      "features": feats}, timeout=60)
            with lock:
                results.append(code)

    threads = [threading.Thread(target=client) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        os.kill(p1.pid, signal.SIGKILL)    # the storm
        p1.wait()
        for t in threads:
            t.join(timeout=60)
        assert len(results) == 36
        assert results == [200] * 36, "requests lost across the kill"
        # restart the killed backend: warm boot from the shared
        # persistent compile cache, router health marks it ready
        t0 = time.monotonic()
        p1, port1_new = spawn()
        warm_boot_s = time.monotonic() - t0
        r.backends[0].port = port1_new
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if r.check_health() == 2:
                break
            time.sleep(0.05)
        assert r.check_health() == 2, "restarted backend never ready"
        code, _, _ = _post(base, {"model": "m0", "features": feats})
        assert code == 200
        assert warm_boot_s < 120  # sanity: the boot completed at all
    finally:
        r.stop()
        for p in (p1, p2):
            try:
                p.stdin.close()
                p.wait(timeout=10)
            except Exception:
                p.kill()
