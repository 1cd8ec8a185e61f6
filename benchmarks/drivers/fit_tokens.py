"""The ``fit_tokens`` driver: one run of a cell whose traffic is
``net.fit()`` of a language model on token ids, on one chip, through the
path a user gets by default. The same set-up / window / trace /
reference sequence as ``drivers/fit.py`` and the same result object;
what differs is what that driver cannot do without an edit:

    inputs     ids and integer labels (``harness/token_train.py``), not
               one-hot arrays
    weights    the configuration's file is the model's own config.json
               keys at the top level; ``program.args`` maps them to the
               zoo function's arguments
    readings   the starting weights of the parameter-change reading are
               made again from the seed inside the program that takes
               the norms, never kept as a second device copy: weights
               and Adam's moments fill over half of the chip
    routing    after the window the expert layers' statistics are read
               from the net's state and published on the metrics
               registry (``publish_routing_metrics``); the per-layer
               metrics read them from ``ctx["routing"]``
"""

import gc
import sys
import tempfile
import time

import numpy as np

from benchmarks.harness import compare, device as device_mod, token_train
from benchmarks.harness.spec import load_module

_fit = load_module("drivers", "fit")
say, seed_key, sized = _fit.say, _fit.seed_key, _fit.sized


def lookup(cfg, path):
    """``cfg["a"]["b"]`` for ``"a.b"``; a list comes back as a tuple."""
    for part in path.split("."):
        cfg = cfg[part]
    return tuple(cfg) if isinstance(cfg, list) else cfg


def build_program(cfg, seed):
    """The program's network for this configuration, through its zoo.
    A tree without the zoo function fails here, before any device
    work."""
    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    prog = cfg["program"]
    make = getattr(zoo, prog["zoo"])
    kwargs = {k: lookup(cfg, path) for k, path in prog["args"].items()}
    conf = make(
        **kwargs, **prog["kwargs"], updater=cfg["updater"]["name"],
        learning_rate=cfg["updater"]["learning_rate"],
        seed=int(seed) % (2 ** 31 - 1))
    schedule = cfg["updater"].get("schedule")
    if schedule:
        conf = _fit.with_schedule(
            conf, {int(k): float(v) for k, v in schedule.items()})
    return MultiLayerNetwork(conf)


def labels_ahead(cfg):
    """Label positions a row carries beyond its inputs' next ids: one
    more for each prediction module."""
    return 1 + int(cfg.get("num_nextn_predict_layers", 0))


class ChunkRecorder:
    """``drivers/fit.py``'s recorder, taking the first chunk's
    readings through ``token_train.take_readings``."""

    supports_batched_iterations = True

    def __init__(self, chunk, take):
        self.chunk = chunk
        self.take = take
        self.losses = []
        self.readings = None

    def iteration_done(self, model, iteration):
        self.losses.append(float(model.score_value))
        if len(self.losses) == self.chunk:
            self.readings = self.take(
                self.losses, model.params,
                _fit.first_moment(model.updater_state))


class FitRun:
    """The set-up of one run (``drivers/fit.py``'s ``FitRun`` for a
    token cell)."""

    def __init__(self, cell, args):
        import jax

        from deeplearning4j_tpu.compile import (
            cache_stats,
            enable_persistent_cache,
            install_cache_accounting,
        )

        self.cfg = sized(cell.config, args.rehearse)
        self.traffic = sized(cell.traffic, args.rehearse)
        self.net = build_program(self.cfg, args.seed)
        self.devices = device_mod.check_device(cell.chips, args.rehearse)
        self.dev = self.devices[0]
        self.cache_dir = enable_persistent_cache()
        install_cache_accounting()
        self.cache_stats = cache_stats
        self.ref = cell.reference()
        self.batch = self.traffic["batch"]
        self.queue_depth = self.traffic["queue_depth"]
        self.make_weights = jax.jit(
            lambda k: self.ref.init(self.cfg, k)[0])
        say("device", platform=self.dev.platform,
            kind=self.dev.device_kind, count=len(self.devices),
            compile_cache_dir=self.cache_dir, workload=cell.name,
            rehearse=args.rehearse)

    def start(self, seed, warmup_chunks=None):
        """Weights and batches from ``seed``, then the warm-up
        ``fit()`` of whole chunks; the first chunk's readings are kept
        in ``self.program``. Called again with another seed it re-uses
        the network and its compiled programs (calibration only)."""
        self.key = seed_key(seed)
        self.net.init(params=self.make_weights(self.key))
        self.net.iteration_count = 0
        self.chunk = self.net.scan_chunk
        say("weights", made_on=str(self.dev))
        self.batches = token_train.make_batches(
            self.cfg["input"], self.batch, self.traffic["host_batches"],
            seed, self.cfg["vocab_size"], labels_ahead(self.cfg))
        say("batches", n=len(self.batches),
            kib_each=round((self.batches[0].features.nbytes
                            + self.batches[0].labels.nbytes) / 2 ** 10, 1))
        recorder = ChunkRecorder(
            self.chunk,
            lambda *a: token_train.take_readings(
                self.ref, self.cfg, self.key, *a))
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
        return _fit.fit_window(
            self.net, self.batches, self.chunk, self.queue_depth,
            seconds=seconds, n_batches=n_batches, pace=pace)

    def free_program(self):
        """Drop the network, so that the reference has the chip."""
        self.net = None
        gc.collect()

    def release_arrays(self):
        """Drop the network's arrays and keep its compiled programs
        (calibration: the reference then has the chip, and ``start``
        makes the arrays anew)."""
        self.net.params = self.net.updater_state = None
        self.net.state = {}
        gc.collect()

    def reference_readings(self, compute="float32", fault=None):
        return token_train.run_reference(
            self.ref, self.cfg, self.key, self.batches, self.chunk,
            compute=compute, fault=fault)


def routing_totals(net):
    """The expert layers' statistics since ``init()``, published on the
    metrics registry and summed over the layers: what the routing
    metrics read."""
    from deeplearning4j_tpu.nn.layers import publish_routing_metrics
    from deeplearning4j_tpu.observability.metrics import default_registry

    layers = publish_routing_metrics(net)
    reg = default_registry()

    def family(name):
        fam = reg.get(name)
        return {"/".join(c.label_values): float(c.value)
                for c in fam.children()} if fam else {}

    return {
        "layers": layers,
        "moe_token_slots_total": family("moe_token_slots_total"),
        "moe_dropped_tokens_total": sum(
            family("moe_dropped_tokens_total").values()),
        "moe_expert_load_max_over_mean": family(
            "moe_expert_load_max_over_mean"),
    }


def run(cell, args, t_start):
    """One run of the cell; returns the result object ``run.py`` prints."""
    import jax

    fr = FitRun(cell, args)
    dev, traffic, batch = fr.dev, fr.traffic, fr.batch
    counts0, stats0 = _fit.dispatch_counts(), fr.cache_stats()
    warm_s, warm_batches, warm_losses = fr.start(args.seed)
    net, chunk = fr.net, fr.chunk
    stats1 = fr.cache_stats()
    say("setup", seed=args.seed, batch=batch, scan_chunk=chunk,
        params_m=round(net.num_params() / 1e6, 2),
        layer_runs=list(net._active_layer_runs()),
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
            for k, v in _fit.dispatch_counts().items()},
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
    routing = routing_totals(net)
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
    say("routing", **{k: v for k, v in routing.items() if k != "layers"})
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
    correct = (correct and bool(np.isfinite(loss_last))
               and routing["moe_dropped_tokens_total"] == 0)
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
               "counts": cell.counts(), "routing": routing}
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
