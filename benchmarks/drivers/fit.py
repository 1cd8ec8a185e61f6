"""The ``fit`` driver: one run of a cell whose traffic is ``net.fit()``
on one chip, through the path a user gets by default.

    set-up   device check, compile cache, weights made on the device
             from the seed in one jitted call, a fixed set of host
             batches from the seed, then a warm-up ``fit(feed,
             epochs=1)`` of whole scan chunks: the first chunk, under a
             listener that keeps the scan path, gives the readings that
             decide ``correct``; the same net goes on to the window
    window   one ``net.fit(PrefetchIterator(DeadlineFeed(...)),
             epochs=1)`` with no listener, clock stopped once the
             parameters are ready; under ``--trace 1`` the same, shorter,
             inside ``jax.profiler.trace``
    after    memory peak read, the program's state freed, the plain
             reference driven through the first chunk's steps, and the
             two compared (``harness/compare.py``)
"""

import gc
import json
import sys
import tempfile
import time

import numpy as np

from benchmarks.harness import compare, device as device_mod
from benchmarks.harness.spec import load_module


_T0 = time.perf_counter()


def say(phase, **fields):
    """An earlier line: what is worth reading and is not a metric, with
    the seconds since this module was loaded."""
    fields = {"t": round(time.perf_counter() - _T0, 2), **fields}
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def sized(block, rehearse):
    """A file's blocks, with its ``tiny`` block laid over them for the
    benchmark's own CPU tests."""
    out = {k: v for k, v in block.items() if k != "tiny"}
    if rehearse:
        out.update(block.get("tiny", {}))
    return out


def with_schedule(conf, schedule):
    """``conf`` with the learning-rate policy ``Schedule`` ({iteration:
    rate}) on every layer: the program's own option, which its zoo does
    not pass through. The rates of a chunk are an input of the compiled
    scan program, so the schedule changes no program."""
    import dataclasses

    def scheduled(layer):
        return dataclasses.replace(
            layer, lr_policy="Schedule", lr_schedule=dict(schedule))

    if hasattr(conf, "layers"):
        return dataclasses.replace(
            conf, layers=tuple(scheduled(l) for l in conf.layers))
    return dataclasses.replace(conf, vertices={
        name: dataclasses.replace(v, layer_conf=scheduled(v.layer_conf))
        if getattr(v, "layer_conf", None) is not None else v
        for name, v in conf.vertices.items()})


def build_program(cfg, seed):
    """The program's network for this configuration, through its zoo."""
    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    prog = cfg["program"]
    kwargs = {k: cfg["model"][k] for k in prog["model_keys"]}
    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in kwargs.items()}
    conf = getattr(zoo, prog["zoo"])(
        **kwargs, **prog["kwargs"], updater=cfg["updater"]["name"],
        learning_rate=cfg["updater"]["learning_rate"],
        seed=int(seed) % (2 ** 31 - 1))
    schedule = cfg["updater"].get("schedule")
    if schedule:
        conf = with_schedule(
            conf, {int(k): float(v) for k, v in schedule.items()})
    engine = {"ComputationGraph": ComputationGraph,
              "MultiLayerNetwork": MultiLayerNetwork}[prog["engine"]]
    return engine(conf)


def dispatch_counts():
    """``pallas_dispatch_total`` as {"kernel/mode": count}."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    family = default_registry().get("pallas_dispatch_total")
    if family is None:
        return {}
    return {"/".join(c.label_values): int(c.value)
            for c in family.children()}


def first_moment(updater_state):
    """{layer: {param: first updater state array}} of the program."""
    return {ln: {pn: st[0] for pn, st in lp.items() if st}
            for ln, lp in updater_state.items()}


class ChunkRecorder:
    """Keeps every step's loss and, once the first chunk's steps are
    done, the readings of that chunk. It declares
    ``supports_batched_iterations``, so ``fit()`` stays on the scan
    path and runs the window's own compiled program."""

    supports_batched_iterations = True

    def __init__(self, chunk, params0):
        self.chunk = chunk
        self.params0 = params0
        self.losses = []
        self.readings = None

    def iteration_done(self, model, iteration):
        from benchmarks.harness.reference_train import take_readings

        self.losses.append(float(model.score_value))
        if len(self.losses) == self.chunk:
            self.readings = take_readings(
                self.losses, self.params0, model.params,
                first_moment(model.updater_state))
            self.params0 = None


def fit_window(net, batches, chunk, queue_depth, *, seconds=None,
               n_batches=None, pace=True):
    """One ``fit(feed, epochs=1)`` of whole chunks and its clock:
    ``(seconds, batches taken, seconds fit() waited for input, seconds
    the pacer held the feed back)``. ``pace=False`` is for
    ``tools/pacer_ab.py`` alone."""
    import jax

    from benchmarks.harness.feed import (
        ChunkPacer,
        DeadlineFeed,
        TimedIterator,
    )
    from deeplearning4j_tpu.datasets.prefetch import PrefetchIterator

    t0 = time.perf_counter()
    feed = DeadlineFeed(
        batches, chunk, n_batches=n_batches,
        deadline=None if seconds is None else t0 + seconds)
    timed = TimedIterator(PrefetchIterator(feed, queue_depth=queue_depth),
                          chunk=chunk,
                          pace=ChunkPacer(net) if pace else None)
    try:
        net.fit(timed, epochs=1)
        jax.block_until_ready(net.params)
        t1 = time.perf_counter()
    finally:
        timed.shutdown()
    return t1 - t0, timed.taken, timed.wait_s, timed.paced_s


class FitRun:
    """The set-up of one run, kept as one object: the program's network
    with its compiled scan program and state, driven from the seed
    through its first chunks, and then handed to the window."""

    def __init__(self, cell, args):
        import jax

        from deeplearning4j_tpu.compile import (
            cache_stats,
            enable_persistent_cache,
            install_cache_accounting,
        )

        self.devices = device_mod.check_device(cell.chips, args.rehearse)
        self.dev = self.devices[0]
        self.cache_dir = enable_persistent_cache()
        install_cache_accounting()
        self.cache_stats = cache_stats
        self.cfg = sized(cell.config, args.rehearse)
        self.traffic = sized(cell.traffic, args.rehearse)
        self.ref = cell.reference()
        self.batch = self.traffic["batch"]
        self.queue_depth = self.traffic["queue_depth"]
        self.make_weights = jax.jit(
            lambda k: self.ref.init(self.cfg, k)[0])
        self.net = None
        say("device", platform=self.dev.platform,
            kind=self.dev.device_kind, count=len(self.devices),
            compile_cache_dir=self.cache_dir, workload=cell.name,
            rehearse=args.rehearse)

    def start(self, seed, warmup_chunks=None):
        """Weights and batches from ``seed``, then the warm-up
        ``fit()`` of whole chunks; the first chunk's readings are kept
        in ``self.program``. Called again with another seed it re-uses
        the network and its compiled programs (calibration only)."""
        from benchmarks.harness import data

        self.key = seed_key(seed)
        weights = self.make_weights(self.key)
        if self.net is None:
            self.net = build_program(self.cfg, seed).init(params=weights)
        else:
            self.net.init(params=weights)
            self.net.iteration_count = 0
        self.chunk = self.net.scan_chunk
        say("weights", made_on=str(self.dev))
        self.batches = data.make_batches(
            self.cfg["input"], self.batch, self.traffic["host_batches"],
            seed)
        say("batches", n=len(self.batches),
            mib_each=round((self.batches[0].features.nbytes
                            + self.batches[0].labels.nbytes) / 2 ** 20, 1))
        recorder = ChunkRecorder(self.chunk, self.make_weights(self.key))
        self.net.set_listeners(recorder)
        chunks = warmup_chunks or self.traffic["warmup_chunks"]
        try:
            warm_s, taken, _, _ = self.window(n_batches=chunks * self.chunk)
        finally:
            self.net.set_listeners()
        if len(recorder.losses) != taken or recorder.readings is None:
            raise AssertionError(
                f"warm-up took {taken} batches but the listener saw "
                f"{len(recorder.losses)} optimizer steps")
        self.program = recorder.readings
        return warm_s, taken, recorder.losses

    def window(self, seconds=None, n_batches=None, pace=True):
        return fit_window(self.net, self.batches, self.chunk,
                          self.queue_depth, seconds=seconds,
                          n_batches=n_batches, pace=pace)

    def free_program(self):
        """Drop the network, so that the reference has the chip."""
        self.net = None
        gc.collect()

    def reference_readings(self, compute="float32", fault=None):
        from benchmarks.harness import reference_train

        return reference_train.run_reference(
            self.ref, self.cfg, self.key, self.batches, self.chunk,
            compute=compute, fault=fault)


def run(cell, args, t_start):
    """One run of the cell; returns the result object ``run.py`` prints."""
    import jax

    fr = FitRun(cell, args)
    dev, traffic, batch = fr.dev, fr.traffic, fr.batch
    counts0, stats0 = dispatch_counts(), fr.cache_stats()
    warm_s, warm_batches, warm_losses = fr.start(args.seed)
    net, chunk = fr.net, fr.chunk
    stats1 = fr.cache_stats()
    say("setup", seed=args.seed, batch=batch, scan_chunk=chunk,
        params_m=round(net.num_params() / 1e6, 2),
        warmup_batches=warm_batches, warmup_s=round(warm_s, 2),
        warmup_losses_first_chunk=[round(v, 4)
                                   for v in warm_losses[:chunk]],
        warmup_loss_last=warm_losses[-1],
        compile_or_load_s=round(
            stats1["compile_seconds"] - stats0["compile_seconds"], 2),
        compile_cache_hits=stats1["hits"] - stats0["hits"],
        compile_cache_misses=stats1["misses"] - stats0["misses"],
        pallas_dispatch_total={
            k: v - counts0.get(k, 0)
            for k, v in dispatch_counts().items()},
        scan_program_built=getattr(net, "_jit_multi_step", None)
        is not None,
        per_step_program_built=getattr(net, "_jit_step", None)
        is not None)

    # -- the window -----------------------------------------------------
    seconds = args.seconds
    trace = None
    steps0 = net.iteration_count
    setup_s = time.perf_counter() - t_start
    if args.trace:
        from benchmarks.harness import trace_reduce

        seconds = min(seconds, traffic["trace_seconds"])
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
            with jax.profiler.trace(tdir):
                window_s, taken, wait_s, paced_s = fr.window(seconds)
            stats2 = fr.cache_stats()
            t_read = time.perf_counter()
            trace = trace_reduce.reduce_dir(tdir, window_s)
            say("trace", read_s=round(time.perf_counter() - t_read, 2),
                **trace_reduce.summary(trace))
    else:
        window_s, taken, wait_s, paced_s = fr.window(seconds)
        stats2 = fr.cache_stats()
    steps = net.iteration_count - steps0
    loss_last = float(net.score_value)
    devices = fr.devices
    peak, reserved = device_mod.memory_peak_bytes(devices[:cell.chips])
    window = {
        "seconds": window_s, "steps": steps, "batches": taken,
        "examples": taken * batch, "batch": batch, "chunk": chunk,
        "feed_wait_s": wait_s, "paced_s": paced_s,
        "compiles": (stats2["hits"] - stats1["hits"])
        + (stats2["misses"] - stats1["misses"]),
    }
    say("window", **window, chunks=taken // chunk, loss_last=loss_last,
        setup_s=round(setup_s, 2), memory_peak_bytes=peak,
        memory_reserved_peak_bytes=reserved,
        memory_peak_share=(round(peak / device_mod.peaks_of(dev)
                                 ["hbm_bytes"], 3)
                           if dev.platform == "tpu" else None))
    say("memory_stats", **(devices[0].memory_stats() or {}))
    if steps != taken or taken % chunk:
        raise AssertionError(
            f"the window took {taken} batches in {steps} optimizer "
            f"steps; whole chunks of {chunk} were expected")

    # -- the reference, once the program's state is freed ---------------
    del net
    fr.free_program()
    t_ref = time.perf_counter()
    reference = fr.reference_readings()
    values, where = compare.numbers(
        fr.program, reference, traffic["loss_steps"])
    correct, checks = compare.decide(values, traffic["limits"])
    correct = correct and bool(np.isfinite(loss_last))
    say("reference", seconds=round(time.perf_counter() - t_ref, 2),
        losses=[round(v, 4) for v in reference["losses"]],
        readings=values, worst_leaves=where, counted_leaves=len(
            compare.counted_leaves(reference)),
        all_leaves=len(reference["grad1"]))

    # -- the result -----------------------------------------------------
    measured = {"fit_examples_per_s": window["examples"] / window_s,
                "setup_s": setup_s}
    result = {"correct": correct, "attempted": steps,
              "failed": 0 if np.isfinite(loss_last) else steps}
    device = device_mod.describe(devices)
    device["memory_peak_bytes"] = peak
    device["memory_reserved_peak_bytes"] = reserved
    if args.trace:
        ctx = {"window": window, "trace": trace, "device": dev,
               "memory_peak_bytes": peak, "cfg": fr.cfg,
               "counts": cell.counts()}
        metrics = {}
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = window_s
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace["top_ops"][:10], "idle_gaps": []}
    else:
        result["metrics"] = {
            m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr, flush=True)
    return result
