#!/usr/bin/env python
"""Serving micro-batch smoke benchmark (CPU, seeded, few seconds).

Drives ``ModelServer.submit()`` directly — the serving hot path
(admission -> queue -> drain -> predict -> response) minus socket
I/O, so the number isolates what micro-batching changes rather than
stdlib HTTP overhead — with a seeded synthetic closed-loop load at
fixed concurrency, once in solo mode (``micro_batch=False``, the
PR-2 one-predict-per-request loop) and once micro-batched. Prints
ONE JSON line::

    {"concurrency": 32,
     "solo":    {"req_per_s": ..., "p50_ms": ..., "p99_ms": ...,
                 "p50_ms_c1": ...},
     "batched": {"req_per_s": ..., "p50_ms": ..., "p99_ms": ...,
                 "p50_ms_c1": ..., "mean_batch_rows": ...,
                 "batches_total": ..., "xla_compiles_total": ...,
                 "post_warmup_compiles_total": ...},
     "speedup": ...}

The acceptance gates this makes falsifiable on CPU:

- ``speedup`` >= 4: one wide XLA dispatch per coalesced batch beats
  per-request dispatch at concurrency 32;
- ``post_warmup_compiles_total`` == 0: steady bucketed load compiles
  nothing after the eager warmup;
- ``p50_ms_c1`` (batched) is no worse than solo at concurrency 1:
  the adaptive batcher dispatches immediately when nothing else is
  in flight.

Runnable standalone (``python scripts/bench_serving.py``).

Fleet mode (``--fleet N``) measures the multi-tenant serving fleet:
N backend server processes (each serving ``--tenants`` named models
with quotas and a paging budget) behind one ``ServingRouter``,
driven closed-loop over real HTTP at fixed TOTAL concurrency, then
the same load against a single backend through the same router path
(so the comparison isolates process-level parallelism, not router
overhead). Prints ONE JSON line::

    {"fleet": {"processes": N, "req_per_s": ..., "per_tenant":
               {"m0": {"p50_ms": ..., "p99_ms": ...}, ...},
               "paging": {...}, "xla_compiles_total": ...},
     "single": {"req_per_s": ...},
     "scaling": fleet_req_per_s / single_req_per_s,
     "cpu_count": ...}

``scaling`` approaches the process count only when the host has the
cores to back it — on a 1-core CI box the processes time-share and
the honest number is ~1; ``cpu_count`` rides along so the reader
can tell the difference.
"""

import argparse
import http.client
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def _make_net(seed=0, n_in=64, hidden=1024, n_out=8):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
        .layer(DenseLayer(n_in=hidden, n_out=hidden,
                          activation="tanh"))
        .layer(OutputLayer(n_out=n_out))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _drive(server, feats_pool, concurrency, per_thread):
    """Closed-loop load: each of ``concurrency`` threads submits
    ``per_thread`` requests back to back. Returns (req/s, p50 ms,
    p99 ms) over the whole run."""
    lat_per_thread = [[] for _ in range(concurrency)]
    errors = []

    def worker(tid):
        lats = lat_per_thread[tid]
        n = len(feats_pool)
        for i in range(per_thread):
            f = feats_pool[(tid * per_thread + i) % n]
            t0 = time.perf_counter()
            code, _, _ = server.submit(f)
            lats.append(time.perf_counter() - t0)
            if code != 200:
                errors.append(code)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(
            f"{len(errors)} non-200 responses (first: {errors[0]})"
        )
    lats = sorted(v for lst in lat_per_thread for v in lst)
    total = concurrency * per_thread

    def pct(q):
        return lats[min(len(lats) - 1, int(q * len(lats)))] * 1000.0

    return total / wall, pct(0.50), pct(0.99)


def run(concurrency=32, per_thread=40, seed=0,
        max_batch_size=64, batch_timeout_ms=8.0, windows=3) -> dict:
    from deeplearning4j_tpu.serving import ModelServer

    net = _make_net(seed=seed)
    rng = np.random.RandomState(seed)
    feats_pool = [rng.rand(1, 64).astype(np.float32)
                  for _ in range(256)]
    out = {"concurrency": concurrency,
           "requests_per_window": concurrency * per_thread,
           "windows": windows}

    kw = dict(workers=4, queue_depth=max(concurrency * 2, 64))
    solo = ModelServer(net, micro_batch=False, **kw).start()
    batched = ModelServer(
        net, max_batch_size=max_batch_size,
        batch_timeout_ms=batch_timeout_ms, **kw,
    ).start()
    best = {solo: None, batched: None}
    try:
        for s in (solo, batched):
            _drive(s, feats_pool, concurrency, 5)  # warm the loop
        # INTERLEAVED same-length windows, best per mode: host noise
        # (scheduler, frequency) drifts over seconds and only ever
        # SLOWS a run, so alternating the
        # modes samples the same conditions for both and the max of
        # N honest end-to-end windows estimates each mode's
        # unimpeded rate
        for _ in range(windows):
            for s in (solo, batched):
                r, p50, p99 = _drive(s, feats_pool, concurrency,
                                     per_thread)
                if best[s] is None or r > best[s][0]:
                    best[s] = (r, p50, p99)
        # concurrency-1 latency: the adaptive batcher must not tax
        # the unloaded path
        c1 = {s: _drive(s, feats_pool, 1, 100)[1]
              for s in (solo, batched)}
        snap = batched.metrics_snapshot()
    finally:
        solo.stop(drain_timeout=2)
        batched.stop(drain_timeout=2)
    for name, s in (("solo", solo), ("batched", batched)):
        r, p50, p99 = best[s]
        out[name] = {"req_per_s": round(r, 1),
                     "p50_ms": round(p50, 3),
                     "p99_ms": round(p99, 3),
                     "p50_ms_c1": round(c1[s], 3)}
    occ = snap.get("batch_occupancy_rows") or {}
    out["batched"].update({
        "batches_total": snap["batches_total"],
        "mean_batch_rows": round(occ.get("mean") or 0.0, 2),
        "xla_compiles_total": snap["xla_compiles_total"],
        "post_warmup_compiles_total":
            snap["post_warmup_compiles_total"],
    })
    out["speedup"] = round(
        out["batched"]["req_per_s"] / out["solo"]["req_per_s"], 2
    )
    return out


# -- fleet mode ---------------------------------------------------------


N_IN_FLEET = 32  # smaller tenant nets: N processes boot in seconds


def _make_tenant_net(idx, seed=0):
    return _make_net(seed=seed + idx, n_in=N_IN_FLEET, hidden=128,
                     n_out=4)


def serve_backend(tenants=4, seed=0, workers=4, queue_depth=128,
                  quota=None, max_device_models=None,
                  preemption_drain=False):
    """``--serve``: one fleet backend process. Serves ``tenants``
    named models (``m0..``) from one ``ModelServer``, prints its port
    as one JSON line, then blocks until stdin closes (the parent's
    handle on our lifetime) — SIGKILL-ing us mid-load is the chaos
    scenario the router must absorb.

    ``--preemption-drain`` installs the resilience tier's
    ``PreemptionHandler`` and translates SIGTERM/SIGINT into the
    graceful drain (in-flight requests finish, new work sheds with
    503), then exits 0 — the preemption-notice chaos storm for the
    serving tier."""
    from deeplearning4j_tpu.serving import ModelServer

    models = {
        f"m{i}": {"model": _make_tenant_net(i, seed), "quota": quota}
        for i in range(tenants)
    }
    server = ModelServer(
        models=models, workers=workers, queue_depth=queue_depth,
        max_batch_size=32,
        max_device_models=max_device_models or None,
    ).start()
    drained = threading.Event()
    if preemption_drain:
        from deeplearning4j_tpu.resilience.preemption import (
            PreemptionHandler,
        )

        handler = PreemptionHandler().install()
        server.install_preemption_drain(handler, drain_timeout=10.0)
        handler.on_preemption(lambda reason: drained.set())
    print(json.dumps({"port": server.port, "pid": os.getpid()}),
          flush=True)
    if preemption_drain:
        # stdin EOF (parent died) on a side thread; the main thread
        # waits for the drain so the process exit code means
        # "drained cleanly", not "killed mid-request"
        eof = threading.Thread(target=sys.stdin.read, daemon=True)
        eof.start()
        while not drained.is_set() and eof.is_alive():
            drained.wait(0.05)
        if not drained.is_set():
            server.stop(drain_timeout=2)
        return
    try:
        sys.stdin.read()  # parent closed our stdin: time to go
    except KeyboardInterrupt:
        pass
    server.stop(drain_timeout=2)


def _spawn_backends(n, tenants, seed, timeout=120.0,
                    max_device_models=0):
    """Start n ``--serve`` children; returns (procs, ports)."""
    script = os.path.abspath(__file__)
    env = dict(os.environ)
    # one shared persistent compile cache: sibling backends load the
    # executables the first one compiled instead of recompiling the
    # same HLO n times (tenant nets differ only in weights)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    procs, ports = [], []
    for i in range(n):
        procs.append(subprocess.Popen(
            [sys.executable, script, "--serve",
             "--tenants", str(tenants), "--seed", str(seed),
             "--max-device-models", str(max_device_models)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=env,
        ))
    try:
        for p in procs:
            deadline = time.monotonic() + timeout
            line = ""
            while time.monotonic() < deadline:
                line = p.stdout.readline()
                if line.strip():
                    break
            ports.append(int(json.loads(line)["port"]))
    except Exception:
        for p in procs:
            p.kill()
        raise
    return procs, ports


def _http_drive(router_port, tenants, concurrency, per_thread,
                seed=0):
    """Closed-loop HTTP load through the router: ``concurrency``
    threads, each pinned to one tenant (round-robin), ``per_thread``
    requests back to back on a keep-alive connection. Returns
    (req/s, {tenant: sorted latency list}, error list)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    feats = [rng.rand(1, N_IN_FLEET).astype(np.float32).tolist()
             for _ in range(64)]
    lat = {f"m{i}": [] for i in range(tenants)}
    lat_lock = threading.Lock()
    errors = []

    def worker(tid):
        tenant = f"m{tid % tenants}"
        mine = []
        conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                          timeout=60)
        try:
            for i in range(per_thread):
                body = json.dumps({
                    "model": tenant,
                    "features": feats[(tid + i) % len(feats)],
                }).encode()
                t0 = time.perf_counter()
                try:
                    conn.request("POST", "/predict", body=body)
                    resp = conn.getresponse()
                    resp.read()
                    code = resp.status
                except OSError:
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", router_port, timeout=60,
                    )
                    code = -1
                mine.append(time.perf_counter() - t0)
                if code != 200:
                    errors.append(code)
        finally:
            conn.close()
        with lat_lock:
            lat[tenant].extend(mine)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return concurrency * per_thread / wall, lat, errors


def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i] * 1000.0


def _scrape(port, path="/metrics"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def run_fleet(processes=4, tenants=4, concurrency=16, per_thread=30,
              seed=0, windows=2) -> dict:
    """Fleet A/B: N processes behind the router vs ONE process behind
    the same router, same total concurrency. Backends run with a
    device budget of tenants-1 models, so the paging stats in the
    JSON are MEASURED under load (one tenant per backend is always
    cold and faults in), not a dormant code path."""
    from deeplearning4j_tpu.serving import ServingRouter

    out = {"cpu_count": os.cpu_count(),
           "tenants": tenants, "concurrency": concurrency,
           "requests_per_window": concurrency * per_thread}
    budget = max(tenants - 1, 1)

    class _Topology:
        def __init__(self, n_backends):
            self.procs, self.ports = _spawn_backends(
                n_backends, tenants, seed, max_device_models=budget,
            )
            self.router = ServingRouter(
                [f"127.0.0.1:{p}" for p in self.ports]
            ).start()
            self.best_rate = None
            self.best_lat = None

        def drive(self, n):
            rate, lat, errors = _http_drive(
                self.router.port, tenants, concurrency, n, seed,
            )
            if errors:
                raise RuntimeError(
                    f"{len(errors)} non-200 through the router "
                    f"(first: {errors[0]})"
                )
            if self.best_rate is None or rate > self.best_rate:
                self.best_rate, self.best_lat = rate, lat

        def teardown(self):
            self.router.stop()
            for p in self.procs:
                p.stdin.close()  # graceful: backend drains, exits
            for p in self.procs:
                if p.wait() is None:
                    p.kill()

    # both topologies live at once, windows INTERLEAVED: host noise
    # on a shared box only ever slows a run, so alternating samples
    # the same conditions for both and the max of N honest windows
    # estimates each topology's unimpeded rate (same design as the
    # solo-vs-batched A/B above)
    fleet = _Topology(processes)
    single = _Topology(1)
    try:
        for t in (fleet, single):
            _http_drive(t.router.port, tenants, concurrency, 5,
                        seed)  # warm the whole path
        for _ in range(windows):
            for t in (fleet, single):
                t.drive(per_thread)
        snaps = [_scrape(p) for p in fleet.ports]
        rsnap = fleet.router.metrics_snapshot()
    finally:
        fleet.teardown()
        single.teardown()
    rate, lat = fleet.best_rate, fleet.best_lat
    srate = single.best_rate
    out["fleet"] = {
        "processes": processes,
        "req_per_s": round(rate, 1),
        "per_tenant": {
            t: {"p50_ms": round(_pct(sorted(v), 0.50), 3),
                "p99_ms": round(_pct(sorted(v), 0.99), 3),
                "requests": len(v)}
            for t, v in lat.items() if v
        },
        "paging": {
            **{k: sum(s["paging"][k] or 0 for s in snaps)
               for k in ("device_resident_models",
                         "device_resident_bytes",
                         "weight_pagein_total",
                         "weight_evict_total")},
            "pagein_p50_ms": round(max(
                ((s["paging"]["weight_pagein_ms"] or {}).get("p50")
                 or 0.0)
                for s in snaps
            ), 3),
        },
        "xla_compiles_total": sum(
            s["xla_compiles_total"] for s in snaps
        ),
        "post_warmup_compiles_total": sum(
            s["post_warmup_compiles_total"] for s in snaps
        ),
        "router": rsnap,
    }
    out["single"] = {"req_per_s": round(srate, 1)}
    out["scaling"] = round(rate / srate, 2)
    if (os.cpu_count() or 1) < processes:
        out["note"] = (
            f"host has {os.cpu_count()} core(s) for {processes} "
            "backend processes: they time-share, so scaling cannot "
            "approach the process count here — rerun on a host with "
            f">= {processes} cores for the parallel number"
        )
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--per-thread", type=int, default=40,
                    help="requests per thread per measured window")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch-size", type=int, default=64)
    ap.add_argument("--batch-timeout-ms", type=float, default=8.0)
    ap.add_argument("--windows", type=int, default=3,
                    help="same-length windows per mode (max wins)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="fleet mode: N backend processes behind a "
                         "router vs 1, same total concurrency")
    ap.add_argument("--tenants", type=int, default=4,
                    help="named models per backend (fleet/serve)")
    ap.add_argument("--serve", action="store_true",
                    help="internal: run one fleet backend process")
    ap.add_argument("--max-device-models", type=int, default=0,
                    help="backend weight-paging budget (0 = no "
                         "paging)")
    ap.add_argument("--preemption-drain", action="store_true",
                    help="with --serve: translate SIGTERM/SIGINT "
                         "into a graceful drain and exit 0")
    args = ap.parse_args()
    if args.serve:
        serve_backend(tenants=args.tenants, seed=args.seed,
                      max_device_models=args.max_device_models,
                      preemption_drain=args.preemption_drain)
        return
    if args.fleet:
        print(json.dumps(run_fleet(
            processes=args.fleet, tenants=args.tenants,
            concurrency=min(args.concurrency, 16),
            per_thread=min(args.per_thread, 30), seed=args.seed,
            windows=min(args.windows, 2),
        )))
        return
    print(json.dumps(run(
        concurrency=args.concurrency, per_thread=args.per_thread,
        seed=args.seed, max_batch_size=args.max_batch_size,
        batch_timeout_ms=args.batch_timeout_ms,
        windows=args.windows,
    )))


if __name__ == "__main__":
    main()
