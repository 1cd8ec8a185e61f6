#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path starts on the chip.

Drives the system once through the entry points a user calls, at the
full width of one model the repo supports (``zoo.resnet50``: 224x224x3,
1000 classes, depths (3, 4, 6, 3), base width 64, bf16 compute over f32
master weights), with random weights and data made from ``--seed``:

    device  jax.devices() must be a TPU, else exit non-zero at once
    train   ComputationGraph(conf).init().fit(PrefetchIterator(
            ListDataSetIterator(batches)), epochs=N): finite loss at
            every step, lower on the last pass than on the first
    serve   write_model -> ModelServer(path, port=0).start() -> real
            HTTP POST /predict at 1 and 8 rows, checked against
            net.output() -> /metrics -> stop()

``--chips 4`` runs instead, and only: the same configuration under
``DistributedTrainer(net, mesh=build_mesh())`` on four chips, and what
it is compared with — the same steps by plain ``fit()`` on chip 0.

This process is the only one that touches JAX (a chip belongs to one
process at a time). No phase is caught and skipped: a failure raises
and the script exits non-zero without printing a result. On success
the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Run it here only through the chip tool; ``--tiny`` shrinks the shapes
for the CPU test of this file's control flow (tests/test_chip_smoke.py).
"""

import argparse
import json
import statistics
import sys
import tempfile
import time
import urllib.request

import numpy as np

FULL = dict(height=224, width=224, channels=3, n_classes=1000,
            depths=(3, 4, 6, 3), base_width=64)
TINY = dict(height=32, width=32, channels=3, n_classes=10,
            depths=(1, 1, 1, 1), base_width=8)

N_BATCHES = 2        # the small fixed set of batches every pass repeats
EPOCHS = 5           # one chip: 10 optimizer steps
DP_EPOCHS = 4        # four chips: 8 steps each for trainer and reference
SERVE_ROWS = (1, 8, 1, 8)
MAX_SERVE_BATCH = 8
# bf16 compute: the served forward and net.output() run the same
# program on the same rows, so this is headroom, not an allowance
SERVE_RTOL, SERVE_ATOL = 5e-2, 1e-3
# sync-BN data parallelism is the single-device trajectory up to the
# order of the cross-chip sums; bf16 rounding then compounds per step
DP_LOSS_RTOL = 5e-2


def check_device(chips: int):
    """The device phase: the devices JAX reports, which must be TPUs
    and at least ``chips`` of them. Nothing here sets or changes
    JAX_PLATFORMS — what the process finds is what it runs on."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no accelerator (platform "
            f"{devices[0].platform!r}); this script proves the chip "
            "path and has no CPU fallback"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
            f"reports {len(devices)}"
        )
    return devices


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


class StepClock:
    """Iteration listener: the loss and the host clock after every
    optimizer step. Reading ``score_value`` waits for the device, so
    the interval between two calls is one whole step."""

    def __init__(self):
        self.losses = []
        self.stamps = []

    def iteration_done(self, model, iteration):
        self.losses.append(float(model.score_value))
        self.stamps.append(time.perf_counter())


def make_conf(shape: dict, seed: int):
    from deeplearning4j_tpu.zoo import resnet50

    return resnet50(
        **shape, seed=seed, learning_rate=0.01,
        dtype="float32", compute_dtype="bfloat16",
    )


def make_batches(shape: dict, batch: int, seed: int):
    from deeplearning4j_tpu.datasets import DataSet

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(N_BATCHES):
        x = rng.standard_normal(
            (batch, shape["channels"], shape["height"], shape["width"]),
            dtype=np.float32,
        )
        classes = rng.integers(0, shape["n_classes"], batch)
        y = np.eye(shape["n_classes"], dtype=np.float32)[classes]
        out.append(DataSet(features=x, labels=y))
    return out


def dispatch_counts() -> dict:
    """``pallas_dispatch_total`` as {"kernel/mode": count}."""
    from deeplearning4j_tpu.observability.metrics import default_registry

    family = default_registry().get("pallas_dispatch_total")
    if family is None:
        return {}
    return {
        "/".join(child.label_values): int(child.value)
        for child in family.children()
    }


def check_losses(losses, per_pass: int) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss in {losses}")
    first, last = losses[:per_pass], losses[-per_pass:]
    if not sum(last) < sum(first):
        raise AssertionError(
            f"loss did not fall on the repeated batches: first pass "
            f"{first}, last pass {last}"
        )


def routed_since(counts_before: dict) -> dict:
    """Kernel routing decisions made since ``counts_before``. A kernel
    in interpret mode is a failure on this path, not a mode."""
    delta = {k: v - counts_before.get(k, 0)
             for k, v in dispatch_counts().items()
             if v != counts_before.get(k, 0)}
    interpreted = {k: v for k, v in delta.items()
                   if k.endswith("/interpret")}
    if interpreted:
        raise AssertionError(
            f"kernels ran in interpret mode on the chip path: "
            f"{interpreted}"
        )
    return delta


def check_kernel_routing(phase: str, counts_before: dict,
                         compiled_text: str) -> None:
    """A Mosaic kernel is in the compiled program exactly when a call
    site routed one there."""
    delta = routed_since(counts_before)
    routed = sum(v for k, v in delta.items() if k.endswith("/pallas"))
    has_kernel = "tpu_custom_call" in compiled_text
    say(phase, pallas_dispatch_total=delta, kernels_routed_pallas=routed,
        tpu_custom_call_in_compiled_step=has_kernel)
    if has_kernel != (routed > 0):
        raise AssertionError(
            f"{routed} kernel call(s) routed pallas but "
            f"tpu_custom_call in the compiled step is {has_kernel}"
        )


def fit_plain(shape, batch, seed, epochs, device):
    """The reference user path: init, listeners, fit on an iterator."""
    import jax

    from deeplearning4j_tpu.datasets import ListDataSetIterator
    from deeplearning4j_tpu.datasets.prefetch import PrefetchIterator
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    net = ComputationGraph(make_conf(shape, seed)).init()
    clock = StepClock()
    net.set_listeners(clock)
    batches = make_batches(shape, batch, seed)
    feed = PrefetchIterator(ListDataSetIterator(batches), queue_depth=2)
    t0 = time.perf_counter()
    try:
        net.fit(feed, epochs=epochs)
    finally:
        feed.shutdown()
    clock.stamps.insert(0, t0)
    off_device = [
        str(leaf.devices()) for leaf in jax.tree.leaves(net.params)
        if leaf.devices() != {device}
    ]
    if off_device:
        raise AssertionError(
            f"params not on {device}: {off_device[:3]}"
        )
    return net, clock, batches


def phase_train(shape, batch, seed, device, cache_dir):
    from deeplearning4j_tpu.compile import cache_stats
    from deeplearning4j_tpu.util.flops import lower_train_step

    counts0, stats0 = dispatch_counts(), cache_stats()
    net, clock, batches = fit_plain(shape, batch, seed, EPOCHS, device)
    stats1 = cache_stats()
    steps = np.diff(clock.stamps)
    check_losses(clock.losses, N_BATCHES)
    say("train", model="zoo.resnet50", **shape, batch=batch,
        params_m=round(net.num_params() / 1e6, 2),
        optimizer_steps=len(clock.losses),
        losses=[round(v, 4) for v in clock.losses])
    say("train",
        first_step_s_compile_included=round(float(steps[0]), 2),
        compile_seconds=round(
            stats1["compile_seconds"] - stats0["compile_seconds"], 2),
        steady_step_ms_median=round(
            statistics.median(steps[2:]) * 1e3, 2),
        steady_steps=len(steps[2:]),
        compile_cache_dir=cache_dir,
        compile_cache_hits=stats1["hits"] - stats0["hits"],
        compile_cache_misses=stats1["misses"] - stats0["misses"],
        params_on=str(device))
    lowered, _ = lower_train_step(net, batches[0])
    check_kernel_routing("train", counts0, lowered.compile().as_text())
    return net, batches


def _http(url: str, payload=None, timeout: float = 300.0):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(
        urllib.request.Request(url, data=data), timeout=timeout
    ) as r:
        return r.status, json.loads(r.read())


def phase_serve(net, batches, workdir: str) -> None:
    from deeplearning4j_tpu.serving import ModelServer
    from deeplearning4j_tpu.util import model_serializer

    path = f"{workdir}/resnet50.zip"
    model_serializer.write_model(net, path)
    rows = batches[0].features
    image = rows.shape[1:]
    counts0 = dispatch_counts()
    # /predict carries [n, d] rows; an image model's server unflattens
    # them in its transform, and warms its buckets from one such row
    server = ModelServer(
        path, port=0, max_batch_size=MAX_SERVE_BATCH,
        transform=lambda flat: np.reshape(flat, (-1,) + image),
        canary=rows[:1].reshape(1, -1),
    ).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        worst = 0.0
        latencies = []
        for n in SERVE_ROWS:
            x = rows[:n]
            t0 = time.perf_counter()
            status, body = _http(
                base + "/predict",
                {"features": x.reshape(n, -1).tolist()},
            )
            latencies.append(round((time.perf_counter() - t0) * 1e3, 1))
            got = np.asarray(body["output"], np.float32)
            want = np.asarray(net.output(x)[0], np.float32)
            if status != 200 or got.shape != want.shape:
                raise AssertionError(
                    f"/predict rows={n}: status {status}, shape "
                    f"{got.shape} against {want.shape}"
                )
            if not np.isfinite(got).all():
                raise AssertionError(f"/predict rows={n}: non-finite")
            if not np.allclose(got, want, rtol=SERVE_RTOL,
                               atol=SERVE_ATOL):
                raise AssertionError(
                    f"/predict rows={n} disagrees with net.output(): "
                    f"max abs diff {np.abs(got - want).max()}"
                )
            worst = max(worst, float(np.abs(got - want).max()))
        _, metrics = _http(base + "/metrics")
    finally:
        server.stop()
    say("serve", requests_rows=list(SERVE_ROWS), all_status=200,
        request_ms_json_included=latencies,
        max_abs_diff_vs_net_output=worst,
        warmup_predicts_total=metrics.get("warmup_predicts_total"),
        xla_compiles_total=metrics.get("xla_compiles_total"),
        post_warmup_compiles_total=metrics.get(
            "post_warmup_compiles_total"),
        compile_cache_dir=server.compile_cache_dir)
    routed_since(counts0)
    if metrics.get("warmup_predicts_total", 0) < 1:
        raise AssertionError("the server never warmed its buckets")
    if metrics.get("post_warmup_compiles_total", 0) != 0:
        raise AssertionError(
            "serving compiled after warm-up: "
            f"{metrics['post_warmup_compiles_total']}"
        )


def phase_data_parallel(shape, batch, seed, devices) -> None:
    """Four chips: DistributedTrainer against plain fit() on chip 0,
    same seed, same batches, same number of steps."""
    import jax

    from deeplearning4j_tpu.datasets import ListDataSetIterator
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.trainer import DistributedTrainer

    n = len(devices)
    _, ref_clock, batches = fit_plain(shape, batch, seed, DP_EPOCHS,
                                      devices[0])
    say("dp", reference="plain fit() on chip 0",
        losses=[round(v, 4) for v in ref_clock.losses])

    counts0 = dispatch_counts()
    net = ComputationGraph(make_conf(shape, seed)).init()
    clock = StepClock()
    net.set_listeners(clock)
    trainer = DistributedTrainer(net, mesh=build_mesh(devices=devices))
    t0 = time.perf_counter()
    trainer.fit(ListDataSetIterator(batches), epochs=DP_EPOCHS,
                prefetch=2)
    clock.stamps.insert(0, t0)
    steps = np.diff(clock.stamps)
    check_losses(clock.losses, N_BATCHES)
    say("dp", trainer=f"DistributedTrainer on {n} chips",
        global_batch=batch,
        losses=[round(v, 4) for v in clock.losses],
        first_step_s_compile_included=round(float(steps[0]), 2),
        steady_step_ms_median=round(
            statistics.median(steps[2:]) * 1e3, 2))

    deviation = max(
        abs(a - b) / max(abs(b), 1e-9)
        for a, b in zip(clock.losses, ref_clock.losses)
    )
    say("dp", max_rel_loss_deviation=deviation, tolerance=DP_LOSS_RTOL)
    if len(clock.losses) != len(ref_clock.losses) or not (
        deviation <= DP_LOSS_RTOL
    ):
        raise AssertionError(
            f"data-parallel losses {clock.losses} left the one-chip "
            f"trajectory {ref_clock.losses} (tolerance {DP_LOSS_RTOL})"
        )

    narrow = [
        len(leaf.sharding.device_set)
        for leaf in jax.tree.leaves(net.params)
        if len(leaf.sharding.device_set) != n
    ]
    if narrow:
        raise AssertionError(
            f"{len(narrow)} param leaves are not on all {n} chips: "
            f"{narrow[:5]}"
        )
    placed = trainer.place_minibatch(batches[0])
    for leaf in placed.features + placed.labels:
        shard_rows = sorted(s.data.shape[0]
                            for s in leaf.addressable_shards)
        if shard_rows != [batch // n] * n:
            raise AssertionError(
                f"batch leaf {leaf.shape} is split {shard_rows}, not "
                f"{n} ways of {batch // n}"
            )
    text = trainer.lower_step(batches[0]).compile().as_text()
    say("dp", param_leaves=len(jax.tree.leaves(net.params)),
        params_device_set=n, batch_leaf_shards=n,
        all_reduce_ops=text.count("all-reduce("))
    if "all-reduce" not in text:
        raise AssertionError("no all-reduce in the compiled step")
    check_kernel_routing("dp", counts0, text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the data-parallel phase and its "
                         "one-chip comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink the shapes (CPU test of this file)")
    args = ap.parse_args(argv)

    devices = check_device(args.chips)
    device = devices[0]
    # the package before the first line of output: beside nothing else
    # of the repo this raises, and nothing has been printed
    from deeplearning4j_tpu.compile import (
        enable_persistent_cache,
        install_cache_accounting,
    )

    say("device", platform=device.platform, kind=device.device_kind,
        count=len(devices), chips_used=args.chips)
    cache_dir = enable_persistent_cache()
    install_cache_accounting()  # compile seconds even with no cache
    shape = TINY if args.tiny else FULL
    batch = 8 if args.tiny else 64
    if args.chips == 4:
        phase_data_parallel(shape, batch, args.seed, devices[:4])
    else:
        net, batches = phase_train(shape, batch, args.seed, device,
                                   cache_dir)
        with tempfile.TemporaryDirectory() as workdir:
            phase_serve(net, batches, workdir)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
