"""Benchmarks for the BASELINE.md target configs, in absolute terms.

Prints ONE JSON line. The primary metric (``metric``/``value``/``unit``
/``vs_baseline``) is config #1 — LeNet-5/MNIST ``fit()`` examples/sec,
the reference's headline number as measured by its PerformanceListener
(``optimize/listeners/PerformanceListener.java:71-86``). The other
configs ride along under ``"configs"`` in the same JSON object.

Every model config also reports ABSOLUTE utilization:
``flops_per_example`` (XLA cost-analysis of the compiled train step —
the FLOPs XLA actually scheduled for forward+backward+updater, not an
analytic estimate), ``achieved_tflops``, and ``mfu`` vs the chip's
bf16 peak (``util/flops.py``; v5e = 197 TFLOP/s). The reference has no
absolute instrument at all, so MFU is where "matching-or-beating" is
falsifiable: the era-small configs (1-4) are dispatch/HBM-shaped by
nature, and the two saturating configs (resnet50_imagenet,
transformer_lm) demonstrate the framework can feed the MXU.

The reference publishes no numbers (BASELINE.md confirms: no perf
claims in README, no benchmarks/ dir), so every ``vs_baseline``
denominator is an ESTIMATE of the nd4j-cuda path on a P100 — the
north-star comparator — derived below. Replace with measured numbers
when they exist.

Baseline derivations (all fp32 P100: 9.3 TFLOP/s peak):

1. lenet_mnist (12,000 ex/s): LeNet-5 fwd+bwd ~36 MFLOP/image;
   DL4J-0.6-era im2col+gemm/cuDNN at batch 64 was dispatch-bound well
   below MXU-class utilization — 12k ex/s (~0.4 TFLOP/s, ~5% of peak)
   matches era reports of small-CNN GPU throughput.
2. vgg16_cifar10 (1,500 ex/s): VGG-16 on 32x32 is ~0.63 GFLOP fwd,
   ~1.9 GFLOP fwd+bwd per image; at ~30% of P100 peak (large convs,
   cuDNN) = 2.8 TFLOP/s -> ~1,500 ex/s.
3. lstm_char_rnn (100,000 chars/s): 2xGravesLSTM(200), vocab 77,
   tbptt 50: ~6.6 MFLOP/char fwd+bwd; LSTM-era effective throughput
   ~0.7 TFLOP/s (small gemms, per-timestep dispatch,
   ``LSTMHelpers.java:159`` loop) -> ~100k chars/s.
4. word2vec_sg (500,000 words/s): hogwild skip-gram
   (``SkipGram.java:244-258`` + native AggregateSkipGram) on a
   multicore host; word2vec-C-class implementations reach
   ~0.3-1M words/s on era hardware.
5. dp_scaling (1.0 = zero overhead): DP sharding/collective overhead
   on the mandated ResNet-50 (CIFAR stem); the reference's Spark
   aggregate round is the analog. Measured as strong scaling at a
   fixed GLOBAL batch on the 8-device virtual CPU mesh (subprocess,
   so the TPU backend stays pristine): total FLOPs are identical with
   1 and 8 devices on the same host cores, so the throughput ratio
   isolates what sharding + psum cost — real multi-chip speedup needs
   real chips and is validated separately by ``dryrun_multichip``.
6. resnet50_imagenet (230 ex/s): ResNet-50 at 224x224 is ~24.6 GFLOP
   fwd+bwd per image (XLA cost-analysis agrees: 23.9G); published
   TF/P100 era numbers are 195-230 ex/s — use 230, the favorable end.
7. transformer_lm (5,000 tokens/s): byte-level decoder LM (d=768,
   L=12, t=512, vocab 256) is ~560 MFLOP fwd+bwd per token (XLA
   cost-analysis); at the same ~30%-of-P100 era-GPU effective rate
   (2.8 TFLOP/s, the assumption of derivations 2 and 6) -> ~5k
   tokens/s. Net-new family (the reference predates attention).

Data placement: every config pre-places its (synthetic or decoded)
dataset in HBM before the measured windows — the same state the
engines' multi-epoch device cache reaches after the first epoch of a
real ``fit``. This measures sustained training throughput with the
host link taken out; what the link costs through the normal iterator
path is the benchmark PR's first question (ROADMAP S3).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

# Persistent XLA compile cache (deeplearning4j_tpu/compile/): every
# section child shares ONE on-disk cache, so ResNet-50-class programs
# compile once per checkout, not once per child process. The place is
# JAX's own JAX_COMPILATION_CACHE_DIR where the caller set it, else the
# fixed <repo>/.jax_cache (compile/persistent.py's rule; spelled out
# here because this parent must not import jax). Children inherit the
# variable (jax reads it at import) and _child_main() additionally
# drops the min-compile-time floor to 0 so small programs cache too,
# and installs hit/miss accounting that lands per-section in the final
# JSON.
_COMPILE_CACHE = os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 ".jax_cache"),
)

BASELINES = {
    "lenet_mnist": 12000.0,        # ex/s    (derivation 1)
    "vgg16_cifar10": 1500.0,       # ex/s    (derivation 2)
    "lstm_char_rnn": 100000.0,     # chars/s (derivation 3)
    "lstm_saturated": 8000.0,      # chars/s (derivation 3b)
    "word2vec_sg": 500000.0,       # words/s (derivation 4)
    "dp_scaling": 1.0,             # linear  (derivation 5)
    "resnet50_imagenet": 230.0,    # ex/s    (derivation 6)
    "transformer_lm": 5000.0,      # tok/s   (derivation 7)
}
# 3b. lstm_saturated: the config-3 architecture at MXU scale (2x
#    GravesLSTM hidden 1024, batch 256, vocab 256): ~84 MFLOP/char
#    fwd+bwd; at the same ~0.7 TFLOP/s era-LSTM effective rate as
#    derivation 3 -> ~8k chars/s.


def _to_hbm(batches):
    """Pre-place a list of DataSets on device (see module docstring:
    the measured windows then exercise the engines' HBM-resident
    path, not the host link)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.api import DataSet

    out = [
        DataSet(
            features=jnp.asarray(b.features),
            labels=jnp.asarray(b.labels),
        )
        for b in batches
    ]
    jax.block_until_ready([b.features for b in out])
    return out


def _is_container_op(name: str) -> bool:
    return (
        name.startswith(("%while", "jit_"))
        or name.isdigit()
        or name == "?"
    )


def _device_step_us(window_fn, n_steps):
    """On-device leaf-op busy time per train step via a jax profiler
    trace of ``window_fn`` (VERDICT r4 #3: wall-clock for
    dispatch-bound configs is dominated by host sync and the host
    link; the xplane device plane records what the chip actually
    executed, so this number is host-independent and falsifiable).
    None when no
    device plane is captured (CPU backend) or the parser is absent."""
    import glob
    import tempfile

    try:
        import jax
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:
        return None
    try:
        with tempfile.TemporaryDirectory() as td:
            jax.profiler.start_trace(td)
            try:
                window_fn()
            finally:
                jax.profiler.stop_trace()
            paths = glob.glob(f"{td}/plugins/profile/*/*.xplane.pb")
            if not paths:
                return None
            sp = xplane_pb2.XSpace()
            with open(sorted(paths)[-1], "rb") as f:
                sp.ParseFromString(f.read())
            busy_ps = 0
            seen = False
            for plane in sp.planes:
                if "TPU" not in plane.name:
                    continue
                meta = {
                    m.id: m.name
                    for m in plane.event_metadata.values()
                }
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    seen = True
                    busy_ps += sum(
                        ev.duration_ps for ev in line.events
                        if not _is_container_op(
                            meta.get(ev.metadata_id, "?")
                        )
                    )
            if not seen or busy_ps == 0:
                return None
            return busy_ps / 1e6 / n_steps
    except Exception as e:
        print(f"device_step_us capture failed: {e!r}", file=sys.stderr)
        return None


def _link_mbps_probe(nbytes=4 << 20) -> float:
    """Measured host->device transfer bandwidth (MB/s) — sizes the
    cold-fit story: if the cold payload stream runs at ~this rate the
    cold number is measuring the link, not the framework."""
    import jax
    import jax.numpy as jnp

    a = np.random.RandomState(0).randint(
        0, 256, nbytes, dtype=np.uint8
    )
    d = jnp.asarray(a)  # warm the path
    jax.block_until_ready(d)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        d = jnp.asarray(a)
        jax.block_until_ready(d)
        _ = np.asarray(d[:1])
        dt = time.perf_counter() - t0
        best = max(best, nbytes / dt / 1e6)
    return round(best, 2)


def _best_rate(fn, n_windows, work):
    """max over same-length windows: interference from the shared host
    only ever slows a run, so the max estimates unimpeded throughput
    (ROADMAP S0(e): report median and quartiles instead). The window
    count and per-window work are fixed, so this is max over N honest
    end-to-end runs, not a shrinking-window trick."""
    rates = []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        rates.append(work / dt)
    return max(rates)


# ---------------------------------------------------------------------------
# 1. LeNet-5 / MNIST (primary)
# ---------------------------------------------------------------------------


def bench_lenet(batch=256, chunk=30, epochs=8) -> dict:
    """Multi-epoch ``fit()`` over an HBM-resident MNIST-sized dataset.

    Features are binarized uint8 pixels (the reference's
    ``MnistDataFetcher(binarize=true)`` mode) transferred at native
    width and cast on device; the multi-epoch fit transfers each fused
    chunk once and re-runs the scanned train step per epoch, so the
    number measures what the reference's PerformanceListener measures —
    sustained ``fit()`` examples/sec — under the TPU-native input
    pipeline rather than a per-batch PCIe copy."""
    from __graft_entry__ import _lenet_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.flops import train_step_cost

    net = MultiLayerNetwork(_lenet_conf()).init()
    net.scan_chunk = chunk
    # one-time dataset materialization (digits->IDX write, sklearn
    # import) happens untimed and ONCE; the timed section is the
    # recurring input pipeline — IDX parse + batch assembly via the
    # native C++ loader — plus the host->device transfer below
    digits_dir = _digits_dir_or_none()
    t0 = time.perf_counter()
    batches, source, n_decoded, make_iter = _mnist_batches(
        batch, chunk, digits_dir
    )
    decode_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    batches = _to_hbm(batches)
    transfer_s = time.perf_counter() - t0
    # small real datasets: cycle the device-resident batches to fill
    # the window (no duplicate transfers)
    batches = [batches[i % len(batches)] for i in range(chunk)]
    flops_ex = train_step_cost(net, batches[0])["flops_per_example"]
    net.fit(batches, epochs=2)  # warmup: compile + one steady epoch
    _ = float(net.score_value)

    def window():
        net.fit(batches, epochs=epochs)
        _ = float(net.score_value)

    rate = _best_rate(window, 3, epochs * chunk * batch)
    # host-independent device time per fused step (LeNet is
    # dispatch-bound by nature; the wall number above carries the
    # host's sync cost)
    dev_us = _device_step_us(
        lambda: (net.fit(batches, epochs=2),
                 float(net.score_value)),
        n_steps=2 * chunk,
    )
    # unoverlapped input cost: host decode (native C++ IDX parse +
    # batch assembly) + host->device transfer, per example, vs the
    # train step; the DevicePrefetchIterator overlaps + 1-bit-packs
    # this — measured below as a COLD fit
    per_ex_input = (decode_s + transfer_s) / max(n_decoded, 1)
    per_ex_train = 1.0 / rate
    cold = _lenet_cold_fit(net, make_iter, n_decoded, batch, chunk)
    out = {
        "value": rate, "flops_per_example": flops_ex,
        "data": source,
        "input_us_per_example_unoverlapped": round(
            per_ex_input * 1e6, 2
        ),
        "input_fraction_unoverlapped": round(
            per_ex_input / (per_ex_input + per_ex_train), 4
        ),
    }
    if dev_us is not None:
        out["device_step_us"] = round(dev_us, 1)
        out["device_examples_per_sec"] = round(batch / dev_us * 1e6, 1)
    out.update(cold)
    if "cold_fit_examples_per_sec" in cold:
        out["cold_fraction_of_cached"] = round(
            cold["cold_fit_examples_per_sec"] / rate, 4
        )
        # is the cold stream link-limited? compare its payload rate
        # to the measured raw link bandwidth (VERDICT r4 #3c)
        link = _link_mbps_probe()
        payload_mbps = (
            cold["cold_fit_examples_per_sec"]
            * cold["cold_payload_bytes_per_example"] / 1e6
        )
        out["link_mbps"] = link
        out["cold_payload_mbps"] = round(payload_mbps, 2)
        out["cold_link_limited"] = bool(payload_mbps > 0.5 * link)
    return out


def _lenet_cold_fit(net, make_iter, n_decoded, batch, chunk) -> dict:
    """COLD ``fit()``: every epoch re-decodes from the source (native
    C++ loader), 1-bit-packs on the prefetch thread, transfers the
    packed payload in ``chunk``-batch groups, and unpacks/one-hots on
    device — decode, transfer and training overlapped (the
    AsyncDataSetIterator analog doing real work). Nothing is reused
    across epochs except compiled code: the epoch count is aligned so
    every fused train dispatch and transfer group has the SAME shape
    (odd leftover chunks would each pay a fresh multi-step compile,
    which on a small dataset dwarfs the streaming itself)."""
    import math

    from deeplearning4j_tpu.datasets import (
        DevicePrefetchIterator,
        MultipleEpochsIterator,
        make_packbits_codec,
    )

    try:
        probe = make_iter()
        d = int(np.shape(probe.next().features)[1])
        enc, dec = make_packbits_codec(d, 10)
        bpe = max(n_decoded // batch, 1)  # full batches per epoch
        # smallest epoch count whose batch stream divides into whole
        # scan_chunk-sized groups
        m = chunk // math.gcd(bpe, chunk)

        def cold(n_epochs):
            # MultipleEpochsIterator INSIDE one prefetch wrapper: the
            # producer thread streams decode->pack->transfer across
            # all epochs without teardown, so fixed costs (thread
            # spin-up, the ~100ms sync read) amortize over the window
            it = DevicePrefetchIterator(
                MultipleEpochsIterator(n_epochs, make_iter()),
                queue_size=4, host_encode=enc, device_decode=dec,
                batch_group=chunk, emit_chunks=True,
            )
            net.fit(it, epochs=1)
            _ = float(net.score_value)

        cold(m)  # warmup: compiles the streamed step + group decode
        t0 = time.perf_counter()
        cold(m)
        per_cycle = time.perf_counter() - t0
        cycles = int(min(max(400 // m, 1),
                         max(1, round(3.0 / max(per_cycle, 1e-4)))))
        n_epochs = m * cycles
        rate = _best_rate(
            lambda: cold(n_epochs), 3, n_epochs * n_decoded
        )
        return {
            "cold_fit_examples_per_sec": round(rate, 1),
            "cold_payload_bytes_per_example": (d + 7) // 8 + 1,
        }
    except Exception as e:
        print(f"cold-fit measurement failed: {e!r}", file=sys.stderr)
        return {"cold_fit_error": str(e)[:300]}


def _digits_dir_or_none():
    """Materialize (once) the bundled real-digits IDX files; failures
    are reported to stderr, not swallowed — the bench then proceeds
    with labeled synthetic data."""
    try:
        from deeplearning4j_tpu.datasets.realdata import ensure_digits_idx

        return ensure_digits_idx()
    except Exception as e:
        print(f"digits-idx materialization failed: {e!r}",
              file=sys.stderr)
        return None


def _mnist_batches(batch, chunk, digits_dir=None):
    """(batches, source, n_decoded, make_iter) for the LeNet bench.
    REAL images are decoded from IDX files through MnistDataSetIterator
    and the native C++ loader: actual MNIST when present
    (DL4J_TPU_MNIST_DIR or ~/.deeplearning4j_tpu/mnist), else the
    bundled real handwritten-digits dataset written-once as IDX
    (``datasets/realdata.py`` — sklearn load_digits, declared as
    such). Synthetic bits are the last resort, labeled in the output.
    Small real datasets are cycled to fill ``chunk``. ``make_iter``
    recreates a fresh decoding iterator over the same source (the
    cold-fit path)."""
    real = _real_idx_batches(batch, chunk, digits_dir)
    if real is not None:
        return real
    from deeplearning4j_tpu.datasets.api import DataSet, ListDataSetIterator

    rng = np.random.RandomState(0)
    batches = [
        DataSet(
            features=(rng.rand(batch, 784) > 0.7).astype(np.uint8),
            labels=np.eye(10, dtype=np.uint8)[
                rng.randint(0, 10, batch)
            ],
        )
        for _ in range(chunk)
    ]
    return (batches, "synthetic", batch * chunk,
            lambda: ListDataSetIterator(batches))


def _real_idx_batches(batch, chunk, digits_dir=None):
    from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator

    def decode(data_dir, source):
        def make_iter(num=batch * chunk):
            return MnistDataSetIterator(
                batch, num_examples=num, binarize=True,
                data_dir=data_dir, allow_synthetic=False,
            )

        full = [
            ds for ds in make_iter() if ds.num_examples() == batch
        ]
        if not full:
            raise ValueError("dataset smaller than one batch")
        n = len(full) * batch
        # the cold iterator decodes exactly the full batches
        return full, source, n, lambda: make_iter(n)

    try:
        return decode(None, "mnist-idx (native C++ decode)")
    except Exception:
        pass  # no (usable) real MNIST -> bundled-digits fallback
    if digits_dir is None:
        return None
    try:
        return decode(
            digits_dir,
            "real-handwritten-digits-idx (sklearn load_digits, "
            "native C++ decode; not MNIST)",
        )
    except Exception as e:
        print(f"digits-idx decode failed: {e!r}", file=sys.stderr)
        return None


# ---------------------------------------------------------------------------
# 2. VGG-16 / CIFAR-10 (ComputationGraph)
# ---------------------------------------------------------------------------


def _vgg16_conf():
    """VGG-16 ComputationGraph over CIFAR-10 (BASELINE.md config #2).
    Pure bf16 — the MXU-native precision; plain-momentum SGD is
    numerically usable in bf16 (unlike Adam's tiny normalized steps).
    The reference comparator is fp32 cuDNN."""
    from deeplearning4j_tpu.zoo import vgg16

    return vgg16(dtype="bfloat16")


def bench_vgg16(batch=128, chunk=16, epochs=4) -> dict:
    """batch 128 (standard for CIFAR VGG training): the larger
    per-step GEMMs keep the MXU fed where small batches are
    dispatch/layout-bound (the batch-64 comparison is not measured on
    the current code).

    chunk=16 (r5): an r5 trace showed the VGG step itself to be a
    small share of a chunk=4 dispatch, the rest dispatch latency, and
    fusing 16 steps per dispatch amortized it. Neither figure has been
    measured on the current code (ROADMAP S2)."""
    import warnings

    from deeplearning4j_tpu.datasets.cifar import CifarDataSetIterator
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.util.flops import train_step_cost

    g = ComputationGraph(_vgg16_conf()).init()
    g.scan_chunk = chunk
    # the CifarDataSetIterator feeds the bench (real batches when the
    # CIFAR-10 binaries are present; the opt-in synthetic set in this
    # egress-less environment — the decode/assemble path is identical)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        it = CifarDataSetIterator(
            batch, num_examples=batch * chunk, allow_synthetic=True,
            seed=0,
        )
    batches = _to_hbm(list(it))
    flops_ex = train_step_cost(g, batches[0])["flops_per_example"]
    g.fit(batches, epochs=2)
    _ = float(g.score_value)

    def window():
        g.fit(batches, epochs=epochs)
        _ = float(g.score_value)

    rate = _best_rate(window, 3, epochs * chunk * batch)
    out = {"value": rate, "flops_per_example": flops_ex}
    dev_us = _device_step_us(
        lambda: (g.fit(batches, epochs=1), float(g.score_value)),
        n_steps=chunk,
    )
    if dev_us is not None:
        out["device_step_us"] = round(dev_us, 1)
        out["device_examples_per_sec"] = round(batch / dev_us * 1e6, 1)
    return out


# ---------------------------------------------------------------------------
# 3. GravesLSTM char-RNN (TBPTT; Pallas LSTM cell on TPU)
# ---------------------------------------------------------------------------


def bench_lstm_char_rnn(batch=32, seq=200, vocab=77, hidden=200,
                        tbptt=50, chunk=10, epochs=8) -> dict:
    """Trains with REAL truncated BPTT (the mode BASELINE.md config #3
    names): length-200 segments chunked at tbptt=50 with the recurrent
    carry threading through a single fused scan per epoch (reset flags
    zero the carry at minibatch boundaries), HBM-cached across
    epochs."""
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.flops import train_step_cost
    from deeplearning4j_tpu.zoo import graves_lstm_char_rnn

    net = MultiLayerNetwork(
        graves_lstm_char_rnn(vocab=vocab, hidden=hidden,
                             tbptt_length=tbptt)
    ).init()
    net.scan_chunk = chunk
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(chunk):
        ids = rng.randint(0, vocab, (batch, seq))
        # uint8 one-hots: the step casts on device, so the host->device
        # transfer is 4x smaller than float32 one-hots
        x = np.eye(vocab, dtype=np.uint8)[ids].transpose(0, 2, 1)
        y = np.eye(vocab, dtype=np.uint8)[
            np.roll(ids, -1, axis=1)
        ].transpose(0, 2, 1)
        batches.append(DataSet(features=x, labels=y))
    batches = _to_hbm(batches)
    # flops/char from ONE tbptt-length chunk (the fused epoch scan
    # runs this same per-chunk program seq/tbptt times per segment)
    cost_ds = DataSet(features=batches[0].features[:, :, :tbptt],
                      labels=batches[0].labels[:, :, :tbptt])
    flops_char = (
        train_step_cost(net, cost_ds)["flops"] / (batch * tbptt)
    )
    net.fit(batches, epochs=2)
    _ = float(net.score_value)

    def window():
        net.fit(batches, epochs=epochs)
        _ = float(net.score_value)

    rate = _best_rate(window, 4, epochs * chunk * batch * seq)
    out = {"value": rate, "flops_per_example": flops_char}
    dev_us = _device_step_us(
        lambda: (net.fit(batches, epochs=2),
                 float(net.score_value)),
        n_steps=2 * chunk,
    )
    if dev_us is not None:
        out["device_step_us"] = round(dev_us, 1)
        out["device_chars_per_sec"] = round(
            batch * seq / dev_us * 1e6, 1
        )
    return out


# ---------------------------------------------------------------------------
# 3b. Saturating LSTM + Pallas-cell A/B (VERDICT r3 #4)
# ---------------------------------------------------------------------------


def bench_lstm_saturated(batch=256, seq=128, vocab=256, hidden=1024,
                         chunk=4, epochs=4) -> dict:
    """The char-RNN architecture at a size that can feed the MXU
    (hidden 1024, batch 256 — per-step gate matmul [256,1024]x
    [1024,4096]), reporting MFU plus an on-chip A/B of the fused
    Pallas LSTM cell against the plain XLA scan cell
    (``DL4J_TPU_PALLAS=1`` vs ``0`` — the era config in config #3 is
    dispatch-bound by nature, so the kernel's value is demonstrated
    here). Reference hot loop this replaces: ``LSTMHelpers.java:159``
    (per-timestep fused ifog gemm)."""
    import jax

    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.flops import train_step_cost
    from deeplearning4j_tpu.zoo import graves_lstm_char_rnn

    rng = np.random.RandomState(0)
    batches = []
    for _ in range(chunk):
        ids = rng.randint(0, vocab, (batch, seq))
        x = np.eye(vocab, dtype=np.uint8)[ids].transpose(0, 2, 1)
        y = np.eye(vocab, dtype=np.uint8)[
            np.roll(ids, -1, axis=1)
        ].transpose(0, 2, 1)
        batches.append(DataSet(features=x, labels=y))
    batches = _to_hbm(batches)

    def run(pallas_flag):
        from deeplearning4j_tpu.ops import dispatch

        prev = os.environ.get("DL4J_TPU_PALLAS")
        os.environ["DL4J_TPU_PALLAS"] = pallas_flag
        # dispatch caches the env read once per process; the A/B flip
        # must go through the explicit test/bench hook
        dispatch.reset_for_tests()
        try:
            net = MultiLayerNetwork(
                graves_lstm_char_rnn(vocab=vocab, hidden=hidden,
                                     tbptt_length=seq)
            ).init()
            net.scan_chunk = chunk
            flops_char = (
                train_step_cost(net, batches[0])["flops"]
                / (batch * seq)
            )
            net.fit(batches, epochs=2)
            _ = float(net.score_value)

            def window():
                net.fit(batches, epochs=epochs)
                _ = float(net.score_value)

            rate = _best_rate(window, 3, epochs * chunk * batch * seq)
            # host-independent: on-device leaf-busy per fused step
            dev_us = _device_step_us(
                lambda: (net.fit(batches, epochs=2),
                         float(net.score_value)),
                n_steps=2 * chunk,
            )
            return rate, flops_char, dev_us
        finally:
            if prev is None:
                os.environ.pop("DL4J_TPU_PALLAS", None)
            else:
                os.environ["DL4J_TPU_PALLAS"] = prev
            dispatch.reset_for_tests()

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        rate_pallas, flops_char, dev_p = run("1")
        rate_xla, _, dev_x = run("0")
        out = {
            # value = the default path (auto -> Pallas kernels on TPU:
            # the whole-sequence VMEM-resident-weights LSTM)
            "value": rate_pallas,
            "flops_per_example": flops_char,
            "pallas_cell_chars_per_sec": round(rate_pallas, 1),
            "xla_scan_cell_chars_per_sec": round(rate_xla, 1),
            "pallas_speedup": round(rate_pallas / rate_xla, 3),
        }
        if dev_p and dev_x:
            # the falsifiable comparison: wall windows carry host
            # sync noise; device-busy time does not
            # (artifacts/lstm_roofline_r5.md)
            out["device_chars_per_sec_pallas"] = round(
                batch * seq / dev_p * 1e6, 1
            )
            out["device_chars_per_sec_xla"] = round(
                batch * seq / dev_x * 1e6, 1
            )
            out["pallas_device_speedup"] = round(dev_x / dev_p, 3)
        return out
    rate, flops_char, _dev = run("auto")  # CPU: no kernel; one number
    return {"value": rate, "flops_per_example": flops_char,
            "note": "non-TPU backend: Pallas A/B skipped"}


# ---------------------------------------------------------------------------
# 4. Word2Vec skip-gram throughput
# ---------------------------------------------------------------------------


def bench_word2vec(n_sentences=5000, sent_len=40, vocab=2000) -> dict:
    from deeplearning4j_tpu.nlp.vocab import VocabConstructor

    # Zipf-ish synthetic corpus, ids pre-resolved (tokenization is
    # host-side prep in both frameworks; the metric is training words/s
    # through the batched skip-gram+negative-sampling XLA path)
    rng = np.random.RandomState(0)
    zipf = 1.0 / np.arange(1, vocab + 1)
    probs = zipf / zipf.sum()
    words = [f"w{i}" for i in range(vocab)]
    sentences = [
        [words[i] for i in rng.choice(vocab, size=sent_len, p=probs)]
        for _ in range(n_sentences)
    ]
    cache = VocabConstructor(
        min_word_frequency=1
    ).build_vocab_from_tokens(sentences)
    from deeplearning4j_tpu.nlp.word2vec import SequenceVectors
    from deeplearning4j_tpu.util.flops import jit_cost

    class _Seq(SequenceVectors):
        def __init__(self, cache, seqs, **kw):
            super().__init__(cache, **kw)
            self._seqs = seqs

        def _sequences(self):
            return iter(self._seqs)

    id_seqs = [
        np.asarray(
            [cache.index_of(w) for w in s if w in cache], np.int32
        )
        for s in sentences
    ]
    B, D, K, W = 16384, 128, 5, 5
    from deeplearning4j_tpu.nlp.word2vec import (
        _dense_rows,
        _sg_device_epochs,
    )

    def make():
        sv = _Seq(
            cache, id_seqs, layer_size=D, window=W, negative=K,
            batch_size=B, epochs=1, seed=1,
        )
        sv.scan_chunk = 64
        sv.device_epoch_gen = True  # on-device epoch generation
        return sv

    sv = make()
    total_words = sum(len(s) for s in id_seqs)
    import jax
    import jax.numpy as jnp

    def sync(v):
        # force completion of every queued update (fit dispatches are
        # async; an unsynced window would time only the enqueue)
        jax.block_until_ready(v.lookup.syn0)
        _ = np.asarray(v.lookup.syn0[:1, :1])  # hard sync: a host read

    sv.fit()  # warmup: compiles the fused generate+train epoch
    sync(sv)
    # flops/word: XLA cost of the one-dispatch epoch program (pair
    # generation is INSIDE the program now, so it is counted)
    ids_d, pos_d, slen_d, kp_d, pool_d, _n = sv._dev_corpus[1]
    nb = ids_d.shape[0] // B
    ep_cost = jit_cost(
        _sg_device_epochs, sv.lookup.syn0, sv.lookup.syn1neg,
        ids_d, pos_d, slen_d, kp_d, pool_d,
        jax.random.PRNGKey(0),
        np.zeros(4, np.float32),
        E=1, W=W, K=K, B=B, dense=_dense_rows(),
    )
    # XLA's cost analysis counts a while-loop body ONCE; the program
    # is 1 epoch x nb batches, so scale by nb for the true epoch cost
    flops_word = ep_cost["flops"] * nb / total_words
    # cold: a FRESH trainer (no device corpus, no warm anything but
    # the process-wide compile cache) — flatten + ONE packed upload +
    # one epoch, end to end; best of 3 fresh trainers (host
    # interference only ever slows a run). The device-gen
    # upload is ~5 bytes/word ONCE, vs the ~90 bytes/word EVERY epoch
    # of the host-generation path that bound r4's cold number.
    cold_s = None
    cold_bytes = 0
    for _ in range(3):
        sv2 = make()
        t0 = time.perf_counter()
        sv2.fit()
        sync(sv2)
        dt = time.perf_counter() - t0
        cold_s = dt if cold_s is None or dt < cold_s else cold_s
        cold_bytes = getattr(sv2, "_dev_upload_bytes", 0)
    reps = 20  # epochs per window: amortize the ~100ms sync read
    sv.epochs = reps  # ONE multi-epoch dispatch per window

    def window():
        sv.fit()
        sync(sv)

    sv.fit()  # warm the multi-epoch executable (E is a shape)
    sync(sv)
    rate = _best_rate(window, 3, reps * total_words)
    return {
        "value": rate, "flops_per_example": flops_word,
        "cold_words_per_sec": round(total_words / cold_s, 1),
        "cold_payload_bytes_per_word": round(
            cold_bytes / total_words, 2
        ),
        "link_mbps": _link_mbps_probe(),
        "measured": "on-device epoch generation (subsampling + windows "
                    "+ negatives + updates all inside ONE multi-epoch "
                    "dispatch from a device-resident corpus), 20 "
                    "epochs/dispatch/window, hard sync at window end; "
                    "cold_words_per_sec = best-of-3 fresh trainers "
                    "incl. corpus flatten + one packed upload + 1 "
                    "epoch",
    }


# ---------------------------------------------------------------------------
# 5. ResNet-50 / 224x224 (BASELINE.md config #5's model, single chip)
# ---------------------------------------------------------------------------


def bench_resnet50(batch=128, chunk=2, epochs=4) -> dict:
    """ResNet-50 v1 at 224x224x3, pure bf16, momentum SGD — the config
    that can actually saturate the MXU (~12 GFLOP/image fwd+bwd). The
    dataset chunk stays HBM-resident across epochs; images ride to the
    device as uint8 and normalize on device."""
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.util.flops import train_step_cost
    from deeplearning4j_tpu.zoo import resnet50

    g = ComputationGraph(
        resnet50(dtype="bfloat16", learning_rate=0.01)
    ).init()
    g.scan_chunk = chunk
    rng = np.random.RandomState(0)
    batches = _to_hbm([
        DataSet(
            features=rng.randint(
                0, 256, (batch, 3, 224, 224), dtype=np.uint8
            ),
            labels=np.eye(1000, dtype=np.uint8)[
                rng.randint(0, 1000, batch)
            ],
        )
        for _ in range(chunk)
    ])
    flops_ex = train_step_cost(g, batches[0])["flops_per_example"]
    g.fit(batches, epochs=1)  # compile (scan-fused epoch) + settle
    _ = float(g.score_value)

    def window():
        g.fit(batches, epochs=epochs)
        _ = float(g.score_value)

    rate = _best_rate(window, 3, epochs * chunk * batch)
    return {"value": rate, "flops_per_example": flops_ex}


# ---------------------------------------------------------------------------
# 6. Transformer byte-LM (flash-attention Pallas kernel on TPU)
# ---------------------------------------------------------------------------


def bench_transformer(batch=16, seq=512, vocab=256, d_model=768,
                      n_layers=12, n_heads=12, chunk=4,
                      epochs=4) -> dict:
    """Decoder-only byte-level LM: d=768, 12 layers, t=512, causal
    flash attention (Pallas kernel on the TPU backend), bf16 compute
    with f32 master weights (Adam needs f32 state). Metric is
    tokens/sec. Net-new vs the reference — this is the long-context
    architecture the char-RNN config grew into."""
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.flops import train_step_cost
    from deeplearning4j_tpu.zoo import transformer_lm

    net = MultiLayerNetwork(transformer_lm(
        vocab=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, compute_dtype="bfloat16", learning_rate=3e-4,
    )).init()
    net.scan_chunk = chunk
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(chunk):
        ids = rng.randint(0, vocab, (batch, seq))
        x = np.eye(vocab, dtype=np.uint8)[ids].transpose(0, 2, 1)
        y = np.eye(vocab, dtype=np.uint8)[
            np.roll(ids, -1, axis=1)
        ].transpose(0, 2, 1)
        batches.append(DataSet(features=x, labels=y))
    batches = _to_hbm(batches)
    flops_tok = (
        train_step_cost(net, batches[0])["flops"] / (batch * seq)
    )
    net.fit(batches, epochs=2)
    _ = float(net.score_value)

    def window():
        net.fit(batches, epochs=epochs)
        _ = float(net.score_value)

    rate = _best_rate(window, 3, epochs * chunk * batch * seq)
    return {"value": rate, "flops_per_example": flops_tok}


# ---------------------------------------------------------------------------
# 7. Data-parallel scaling on the 8-device virtual mesh (subprocess)
# ---------------------------------------------------------------------------

_DP_CHILD = r"""
import json, os, time
import numpy as np
n = int(os.environ["DP_DEVICES"])
b = int(os.environ["DP_BATCH"])
steps = int(os.environ["DP_STEPS"])
# a CPU section by construction: the virtual 8-device mesh of the
# dry run (never a chip measurement)
from __graft_entry__ import _ensure_devices
_ensure_devices(8)
import jax
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel import DistributedTrainer, build_mesh
from deeplearning4j_tpu.zoo import resnet50

# the mandated DP model (BASELINE.md config #5): ResNet-50, CIFAR stem
# on the virtual mesh (224x224 would measure host-core contention, not
# sharding overhead, on 8 virtual devices sharing one CPU).
# batch_stats="local" = the reference's worker semantics (Spark
# workers computed BN stats on their own shard).
conf = resnet50(height=32, width=32, channels=3, n_classes=10,
                cifar_stem=True, learning_rate=0.01)
net = ComputationGraph(conf).init()
mesh = build_mesh(data=n, model=1, devices=jax.devices()[:n])
tr = DistributedTrainer(net, mesh=mesh, batch_stats="local")
rng = np.random.RandomState(0)
ds = DataSet(features=rng.rand(b, 3, 32, 32).astype(np.float32),
             labels=np.eye(10, dtype=np.float32)[rng.randint(0, 10, b)])
for _ in range(2):
    tr.fit_minibatch(ds)
float(net.score_value)
# min over individually-timed steps: host/daemon interference on the
# single shared core only ever ADDS time, so the min estimates the
# uncontended step (same estimator as the throughput windows)
times = []
for _ in range(steps):
    t0 = time.perf_counter()
    tr.fit_minibatch(ds)
    float(net.score_value)
    times.append(time.perf_counter() - t0)
print(json.dumps({"devices": n, "batch": b,
                  "sec_per_step": min(times)}))
"""


def bench_dp_scaling(batch=64, steps=4, budget_s=None) -> dict:
    """ResNet-50 (CIFAR stem) DP overhead on the 8-device virtual CPU
    mesh. The host serializes all virtual devices onto its core(s), so
    total FLOPs executed per step is what costs time and two ratios
    bracket the sharding overhead:

    - WEAK (primary): t(1 dev, b/8) * 8 vs t(8 dev, b) — per-device
      programs are identical, so the shortfall from 1.0 is purely
      partitioning + collectives (with batch_stats="local": one
      gradient pmean per step).
    - STRONG: t(1 dev, b) vs t(8 dev, b) — adds the small-per-device-
      batch kernel-efficiency penalty, which real multi-chip DP at
      constant per-chip batch never pays; reported as detail.
    """
    def run(n, b):
        env = dict(os.environ)
        env.update({
            "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or "",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
            "DP_DEVICES": str(n),
            "DP_BATCH": str(b),
            "DP_STEPS": str(steps),
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(os.path.abspath(__file__))]
                + env.get("PYTHONPATH", "").split(os.pathsep)
            ),
        })
        timeout = 1800
        if budget_s is not None:
            timeout = max(60, min(timeout, int(budget_s)))
        out = subprocess.run(
            [sys.executable, "-c", _DP_CHILD], env=env,
            capture_output=True, text=True, timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(f"dp child failed: {out.stderr[-2000:]}")
        return json.loads(out.stdout.strip().splitlines()[-1])

    one_small = run(1, batch // 8)
    eight = run(8, batch)
    one_full = run(1, batch)
    weak = 8 * one_small["sec_per_step"] / eight["sec_per_step"]
    strong = one_full["sec_per_step"] / eight["sec_per_step"]
    # strong-scaling decomposition (VERDICT r4 #4): strong =
    # small_batch_compute_efficiency x sharding overhead. The first
    # factor is t(1 dev, b) / 8*t(1 dev, b/8) — how much per-example
    # efficiency the b/8 per-device batch loses with ZERO sharding in
    # the program at all; it is the hard floor for fixed-global-batch
    # scaling on the serialized virtual mesh and caps `strong` at
    # that value even with free collectives.
    small_batch_eff = one_full["sec_per_step"] / (
        8 * one_small["sec_per_step"]
    )
    return {
        "sharding_overhead_efficiency": round(weak, 3),
        "weak_scaling_efficiency": round(weak, 3),
        "strong_scaling_efficiency_fixed_global_batch": round(strong, 3),
        "strong_scaling_floor_small_batch_compute": round(
            small_batch_eff, 3
        ),
        "strong_scaling_vs_floor": round(strong / small_batch_eff, 3),
        "sec_per_step_1dev_shard": round(one_small["sec_per_step"], 2),
        "sec_per_step_1dev_full": round(one_full["sec_per_step"], 2),
        "sec_per_step_8dev": round(eight["sec_per_step"], 2),
        "model": "resnet50 cifar-stem, batch_stats=local "
                 "(reference worker semantics)",
    }


_ELASTIC_CHILD = r"""
import json, os, time
import numpy as np
from __graft_entry__ import _ensure_devices
_ensure_devices(8)
import jax
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import ElasticTrainer, build_mesh
from deeplearning4j_tpu.resilience import (CheckpointManager,
    PreemptionHandler, PreemptedException)

conf = (NeuralNetConfiguration.Builder().seed(7).learning_rate(0.05)
        .updater("ADAM").list()
        .layer(DenseLayer(n_in=32, n_out=64, activation="tanh"))
        .layer(OutputLayer(n_out=8)).build())
net = MultiLayerNetwork(conf).init()
rng = np.random.RandomState(0)
bs = [DataSet(features=rng.rand(16, 32).astype(np.float32),
              labels=np.eye(8, dtype=np.float32)[
                  rng.randint(0, 8, 16)])
      for _ in range(12)]

et = ElasticTrainer(net, mesh=build_mesh(), snapshot_every=4)
marks = {}
orig_recover = et.recover
def timed_recover(dead):
    marks["step_at_kill"] = int(net.iteration_count)
    marks["t_kill"] = time.perf_counter()
    snap = orig_recover(dead)
    marks["snap_step"] = snap["step"]
    marks["t_recovered"] = time.perf_counter()
    return snap
et.recover = timed_recover
class _Inject:
    def iteration_done(self, model, it):
        if it == 6 and "injected" not in marks:
            marks["injected"] = True
            et.inject_device_loss([4, 5, 6, 7])
        elif et.recoveries and "t_first_step" not in marks:
            # first completed optimizer step on the survivor mesh
            marks["t_first_step"] = time.perf_counter()
net.listeners.append(_Inject())
et.fit(bs, epochs=1)

# the other half of the crash story: preemption notice -> quiesced
# emergency checkpoint (drain + atomic save) latency
import tempfile
mgr = CheckpointManager(tempfile.mkdtemp())
h = PreemptionHandler(manager=mgr).install()
h.notify("bench")
t0 = time.perf_counter()
try:
    et.fit(bs, epochs=1)
    ckpt_s = None
except PreemptedException:
    ckpt_s = time.perf_counter() - t0
h.uninstall()

print(json.dumps({
    "recovery_s": round(marks["t_recovered"] - marks["t_kill"], 4),
    "time_to_first_step_s": round(
        marks["t_first_step"] - marks["t_kill"], 4),
    "steps_lost": marks["step_at_kill"] - marks["snap_step"],
    "snapshot_every": 4,
    "devices_before": 8, "devices_after": 4,
    "final_step": int(net.iteration_count),
    "emergency_checkpoint_s": (round(ckpt_s, 4)
                               if ckpt_s is not None else None),
}))
"""


def bench_elastic_recovery(budget_s=None) -> dict:
    """Device-loss recovery latency on the 8-device virtual CPU mesh:
    kill half the mesh mid-run, measure declared-dead ->
    survivor-mesh rebuild (``recovery_s``) and -> first completed
    optimizer step on the survivors (``time_to_first_step_s``, which
    includes the re-jit for the new mesh). ``steps_lost`` must stay
    under ``snapshot_every`` — recovery replays from the host-RAM
    snapshot ring, no disk I/O. Also reports the preemption half:
    notice -> drained emergency checkpoint wall time."""
    env = dict(os.environ)
    env.update({
        "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or "",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.abspath(__file__))]
            + env.get("PYTHONPATH", "").split(os.pathsep)
        ),
    })
    timeout = 900
    if budget_s is not None:
        timeout = max(60, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, "-c", _ELASTIC_CHILD], env=env,
        capture_output=True, text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"elastic child failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


_HOST_RECOVERY_WORKER = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.extend.backend as _jeb
_jeb.clear_backends()
try:
    jax.config.update("jax_num_cpu_devices", 1)
except Exception:
    pass
try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass
_jeb.clear_backends()

import numpy as np

from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.control_plane import WorkerAgent
from deeplearning4j_tpu.parallel.elastic import HostElasticTrainer
from deeplearning4j_tpu.parallel.mesh import (
    build_mesh, init_distributed_elastic,
)
from deeplearning4j_tpu.resilience.chaos import KillAtStep

rank = int(os.environ["HR_RANK"])
kill_at = int(os.environ.get("HR_KILL_AT", "-1"))
n_batches = int(os.environ["HR_NBATCH"])
snap_every = int(os.environ["HR_SNAP_EVERY"])

agent = WorkerAgent(os.environ["HR_CONTROL"], rank_hint=rank)
grant = agent.join(timeout_s=60)
agent.start_renewals()
init_distributed_elastic(grant.jax_coordinator, grant.num,
                         grant.rank, timeout_s=60)

conf = (NeuralNetConfiguration.Builder().seed(42).learning_rate(0.05)
        .updater("ADAM").list()
        .layer(DenseLayer(n_in=16, n_out=64, activation="tanh"))
        .layer(OutputLayer(n_out=4, loss="MCXENT"))
        .build())
net = MultiLayerNetwork(conf).init()
mesh = build_mesh(data=len(jax.devices()), model=1)
tr = HostElasticTrainer(net, agent, mesh=mesh,
                        snapshot_every=snap_every)
rng = np.random.RandomState(0)
data = [DataSet(features=rng.randn(32, 16).astype(np.float32),
                labels=np.eye(4, dtype=np.float32)[
                    rng.randint(0, 4, 32)])
        for _ in range(n_batches)]

marks = {}
_recover = tr.recover
def recover(plan):
    marks["t_plan"] = time.monotonic()
    marks["step_at_plan"] = int(net.iteration_count)
    snap = _recover(plan)
    marks["t_recovered"] = time.monotonic()
    return snap
tr.recover = recover

class FirstStepAfterRecovery:
    def iteration_done(self, model, iteration):
        if "t_recovered" in marks and "t_first_step" not in marks:
            marks["t_first_step"] = time.monotonic()

net.listeners.append(FirstStepAfterRecovery())
if kill_at >= 0:
    net.listeners.append(KillAtStep(kill_at))
tr.fit(data, epochs=1)
agent.close()

rec = tr.last_recovery or {}
print(json.dumps({
    "recovery_s": round(marks["t_recovered"] - marks["t_plan"], 4),
    "time_to_first_step_s": round(
        marks["t_first_step"] - marks["t_plan"], 4),
    "steps_lost": marks["step_at_plan"] - rec.get("rolled_back_to", 0),
    "rolled_back_to": rec.get("rolled_back_to"),
    "snapshot_every": snap_every,
    "hosts_before": 2, "hosts_after": rec.get("survivors"),
    "final_step": int(net.iteration_count),
    "recoveries": tr.recoveries,
}))
"""


def bench_host_recovery(budget_s=None) -> dict:
    """HOST-loss recovery latency: two real processes form a
    ``jax.distributed`` CPU mesh under the lease control plane, rank 1
    is SIGKILLed mid-run, and the survivor re-forms a 1-process
    runtime. Measures, on the survivor, plan-received ->
    trainer-rebuilt (``recovery_s``, including the jax runtime
    teardown + re-init) and -> first completed optimizer step on the
    re-formed mesh (``time_to_first_step_s``, including the re-jit).
    ``steps_lost`` must stay under ``snapshot_every``: recovery
    replays from the host-RAM snapshot ring, no disk I/O."""
    from deeplearning4j_tpu.parallel.control_plane import (
        LeaseCoordinator,
    )

    n_batches, snap_every, kill_at = 12, 4, 7
    repo = os.path.dirname(os.path.abspath(__file__))
    timeout = 300
    if budget_s is not None:
        timeout = max(60, min(timeout, int(budget_s)))
    coord = LeaseCoordinator(2, lease_s=1.0,
                             barrier_timeout_s=60.0).start()
    procs = []
    try:
        for rank in range(2):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update({
                "PYTHONPATH": os.pathsep.join(
                    [repo] + env.get("PYTHONPATH", "").split(
                        os.pathsep)),
                "HR_RANK": str(rank),
                "HR_CONTROL": coord.address,
                "HR_NBATCH": str(n_batches),
                "HR_SNAP_EVERY": str(snap_every),
                "HR_KILL_AT": str(kill_at if rank == 1 else -1),
            })
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _HOST_RECOVERY_WORKER],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))
        out0, err0 = procs[0].communicate(timeout=timeout)
        procs[1].wait(timeout=30)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        coord.stop()
    if procs[0].returncode != 0:
        raise RuntimeError(
            f"host-recovery survivor failed: {err0[-2000:]}")
    if procs[1].returncode != -9:
        raise RuntimeError(
            "host-recovery victim was not SIGKILLed "
            f"(rc={procs[1].returncode})")
    return json.loads(out0.strip().splitlines()[-1])


def bench_checkpoint_stall(budget_s=None) -> dict:
    """Write-behind vs synchronous checkpointing: the training-thread
    stall per save. A sync save pays serialize + fsync + commit on
    the training thread; an async save pays only the buffer-isolated
    host snapshot before handing the write to the background writer.
    The acceptance gate is async p99 stall <= 25% of the median sync
    save wall time (in practice the async stall is the host-copy time
    alone, far below the write)."""
    import tempfile

    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.resilience.checkpoint import (
        CheckpointManager, LocalCommitBarrier,
    )

    deadline = (time.monotonic() + budget_s - 10.0
                if budget_s else None)

    def time_left():
        return deadline is None or time.monotonic() < deadline

    # big enough that serialize+write dwarfs the host copy (~6M
    # params -> ~70 MB with the two ADAM moments)
    conf = (
        NeuralNetConfiguration.Builder().seed(7).learning_rate(0.05)
        .updater("ADAM").list()
        .layer(DenseLayer(n_in=512, n_out=2048, activation="tanh"))
        .layer(DenseLayer(n_in=2048, n_out=2048, activation="tanh"))
        .layer(OutputLayer(n_out=10))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(7)
    ds = DataSet(
        features=rng.randn(16, 512).astype(np.float32),
        labels=np.eye(10)[rng.randint(0, 10, 16)].astype(np.float32),
    )
    net.fit_minibatch(ds)  # materialize updater state + compile

    n_sync, n_async = 5, 10
    sync_ms, stall_ms = [], []
    with tempfile.TemporaryDirectory() as td:
        mgr_sync = CheckpointManager(
            os.path.join(td, "sync"), keep_last=2)
        for _ in range(n_sync):
            if not time_left():
                break
            t0 = time.perf_counter()
            mgr_sync.save(net)
            sync_ms.append((time.perf_counter() - t0) * 1000.0)
            net.fit_minibatch(ds)
        mgr_async = CheckpointManager(
            os.path.join(td, "async"), keep_last=2, mode="async",
            commit=LocalCommitBarrier())
        handles = []
        for _ in range(n_async):
            if not time_left():
                break
            t0 = time.perf_counter()
            handles.append(mgr_async.save(net))
            stall_ms.append((time.perf_counter() - t0) * 1000.0)
            # training continues while the writer works — the whole
            # point of write-behind; the wait below is bookkeeping
            # only (keeps every step committed, off the clock)
            net.fit_minibatch(ds)
            handles[-1].wait(120)
        write_p50 = float(
            mgr_async._m_write.snapshot().get("p50") or 0.0)
        mgr_async.stop()
    if not sync_ms or not stall_ms:
        raise RuntimeError("checkpoint_stall ran out of budget "
                           "before collecting samples")
    sync_p50 = float(np.percentile(sync_ms, 50))
    stall_p50 = float(np.percentile(stall_ms, 50))
    stall_p99 = float(np.percentile(stall_ms, 99))
    return {
        "sync_save_ms_p50": round(sync_p50, 3),
        "async_stall_ms_p50": round(stall_p50, 3),
        "async_stall_ms_p99": round(stall_p99, 3),
        "async_write_ms_p50": round(write_p50, 3),
        "stall_ratio_p99": round(stall_p99 / max(sync_p50, 1e-9), 4),
        "saves_measured": {"sync": len(sync_ms),
                           "async": len(stall_ms)},
        "stall_bounded": bool(stall_p99 <= 0.25 * sync_p50),
        "gate": "async_stall_ms_p99 <= 0.25 * sync_save_ms_p50 "
                "(write-behind stalls the training thread for the "
                "host snapshot only)",
    }


# ---------------------------------------------------------------------------
# 8. Serving micro-batch throughput (scripts/bench_serving.py)
# ---------------------------------------------------------------------------


def bench_serving(budget_s=None) -> dict:
    """Batched vs solo serving throughput at concurrency 32 on this
    backend, via the standalone smoke script (subprocess: the load
    generator spins up 30+ client threads and two servers — keep that
    out of the bench process). Reports the script's JSON verbatim;
    the acceptance gates are ``speedup`` >= 4 and
    ``post_warmup_compiles_total`` == 0."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_serving.py",
    )
    timeout = 600
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or ""},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_serving failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_serving_fleet(budget_s=None) -> dict:
    """Multi-tenant fleet throughput: 4 backend processes (each
    serving 4 tenant models with a paging budget) behind the
    ``ServingRouter`` vs 1 backend through the same router path, at
    the same total concurrency, via the standalone script in fleet
    mode (subprocess — it spawns the backend fleet). Reports the
    script's JSON verbatim; the acceptance gates are ``scaling``
    approaching the process count ON A MULTI-CORE HOST (``cpu_count``
    rides along — a 1-core box time-shares the processes and honestly
    reports ~1x) and ``post_warmup_compiles_total`` == 0 across the
    fleet."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_serving.py",
    )
    timeout = 600
    if budget_s is not None:
        timeout = max(60, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script, "--fleet", "4"],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or ""},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_serving --fleet failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_input_pipeline(budget_s=None) -> dict:
    """Synchronous vs pipelined (prefetch + async dispatch) training
    fit on an iterator with nontrivial host-side batch cost, via the
    standalone A/B script (subprocess — it builds its own nets and
    trainers). Reports the script's JSON verbatim; the acceptance
    gates are ``speedup`` > 1 (steps/sec improvement) and
    ``trajectory_match`` == true (the pipeline never changes what is
    trained). ``input_stall_fraction`` per mode is the device-idle-
    on-input proxy."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_training.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or ""},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_training failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_zero_sharding(budget_s=None) -> dict:
    """ZeRO-sharded optimizer state + in-jit gradient accumulation
    A/B via the standalone training script (subprocess — it builds
    its own 8-virtual-device mesh and trainers). Reports the
    script's ``zero_sharding`` and ``grad_accum`` payloads; the
    acceptance gates are ``trajectory_match`` == true (sharding
    never changes the bits trained) and ``updater_bytes_ratio``
    <= 0.25 (per-device optimizer state at most 1/4 of replicated
    on the 8-wide mesh — the train-N×-larger headroom claim)."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_training.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    env = dict(os.environ)
    env.update({
        "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or "",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    })
    out = subprocess.run(
        [sys.executable, script, "--steps", "16", "--io-ms", "0",
         "--zero", "--grad-accum", "4"],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_training --zero failed: {out.stderr[-2000:]}"
        )
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    return {
        "zero_sharding": doc.get("zero_sharding", {}),
        "grad_accum": doc.get("grad_accum", {}),
    }


def bench_megastep(budget_s=None) -> dict:
    """Megastep-epochs A/B via the standalone training script
    (subprocess — per-step fit vs K=6 steps fused into one dispatch
    behind the chunk-mode double-buffered prefetch, on an I/O-bound
    iterator). Reports the script's ``megastep`` payload; the
    acceptance gates are ``dispatches_per_step_megastep`` <= 1.5/K
    (flight-recorder records per optimizer step — the one-dispatch-
    per-chunk claim), ``input_stall_fraction_megastep`` < 0.05 (the
    double-buffered feed keeps the fused dispatch fed), and the
    BITWISE ``trajectory_match`` vs the per-step reference — rolled
    up as ``megastep_ok``."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_training.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script, "--steps", "36", "--io-ms", "0",
         "--windows", "3", "--megastep", "6"],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or "",
             "JAX_PLATFORMS": "cpu"},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_training --megastep failed: {out.stderr[-2000:]}"
        )
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    return doc.get("megastep", {})


def bench_data_defense(budget_s=None) -> dict:
    """Bad-data defense A/B via the standalone training script
    (subprocess — it builds its own nets, validator, quarantine store
    and stat-guard on a realistically sized step). Reports the
    script's ``defense`` payload; the acceptance gates are
    ``overhead_fraction`` <= 0.05 (validator + statistical guard on
    the clean path), ``quarantined_on_clean`` == 0, and the two
    no-trip bitwise lemmas (``validator_bitwise``,
    ``statguard_bitwise``) — rolled up as ``defense_ok``."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_training.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script, "--steps", "16", "--io-ms", "0",
         "--defense"],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or "",
             "JAX_PLATFORMS": "cpu"},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_training --defense failed: {out.stderr[-2000:]}"
        )
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    return doc.get("defense", {})


def bench_aot_compile(budget_s=None) -> dict:
    """Cold vs warm serving boot through the compile-artifact
    subsystem, via the standalone A/B script (subprocess — it boots
    three server child processes). Reports the script's JSON
    verbatim; the acceptance gates are ``zero_compile_warm_restart``
    (the AOT boot performs zero XLA backend compiles, counter-
    asserted) and ``speedup_boot_aot`` > 1 (boot-to-ready materially
    faster than the cold boot)."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_compile.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_compile failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_embeddings(budget_s=None) -> dict:
    """Sharded-embeddings A/B via the standalone script (subprocess —
    it builds its own 8-virtual-device mesh). Reports the script's
    JSON verbatim; the acceptance gates are
    ``residency.bytes_per_device_ratio`` ~ 1/8 (one device holds one
    row shard of the 16 MiB table), ``sparse_update.bitwise_match``
    (the deduped owner-side scatter equals a dense [V, D]-cotangent
    step bit-for-bit) with ``speedup`` > 1 (update cost scales with
    the batch's unique rows, not vocab), and
    ``fused_step.loss_parity`` (the collective-lookup fused NS step
    matches the single-device reference loss) — rolled up as
    ``embeddings_ok`` (the script exits nonzero on a gate failure)."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_embeddings.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    env = dict(os.environ)
    env.update({
        "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or "",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    })
    out = subprocess.run(
        [sys.executable, script, "--budget-s", str(max(30, timeout - 20))],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_embeddings failed (rc {out.returncode}): "
            f"{out.stderr[-2000:] or out.stdout[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _bench_transforms(section: str, budget_s=None) -> dict:
    """``compile_vs_depth`` / ``remat_memory`` via the standalone
    transform A/B script (scripts/bench_transforms.py — every
    measurement is a cold subprocess with the compile cache DISABLED,
    so the reported compiles are real even when this bench child
    shares the persistent cache). Gates: >=2x compile-time reduction
    at depth 64 with scan-over-layers; >=1.5x max-fitting batch (or
    equivalent temp-bytes reduction) with remat on."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_transforms.py",
    )
    timeout = 560
    if budget_s is not None:
        timeout = max(60, min(timeout, int(budget_s)))
    cmd = [sys.executable, script, "--section", section,
           "--budget-s", str(timeout - 20)]
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_transforms {section} failed: {out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_compile_vs_depth(budget_s=None) -> dict:
    return _bench_transforms("compile_vs_depth", budget_s)


def bench_remat_memory(budget_s=None) -> dict:
    return _bench_transforms("remat_memory", budget_s)


def bench_fused_kernels(budget_s=None) -> dict:
    """Pallas fused-kernel library A/B via the standalone script
    (scripts/bench_kernels.py — interleaved kernel vs XLA windows per
    config: conv stack, resnet50 bottleneck, MLP). Gates: kernel
    forward parity <= 1e-5 vs the XLA reference (interpret mode
    exercises the same code path on CPU) and the compiled-op evidence
    that the fused epilogue eliminates the separate bias/BN/activation
    HBM round-trips (executable + entry-op counts, round-trip bytes).
    On CPU the run is correctness-only (``timing_skipped``); on a real
    TPU it also reports step time, achieved FLOP/s and the MFU delta
    per config."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_kernels.py",
    )
    timeout = 300
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script, "--budget-s", str(max(10, timeout - 10))],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or ""},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_kernels failed (parity or fusion-evidence gate): "
            f"{out.stderr[-2000:] or out.stdout[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_kernel_autotune(budget_s=None) -> dict:
    """Autotuner A/B via ``scripts/bench_kernels.py --tuned``: a cold
    ``DL4J_TPU_TUNE=on`` pass searches conv/matmul tilings into a
    fresh cache (heuristic measured first and budget-exempt, winner =
    argmin of the same interleaved timings, so the per-config delta is
    non-negative by construction), then a warm ``cached``-mode pass
    re-resolves every entry from disk with the search and measurement
    counters asserted at ZERO. Gates: non-negative ``tuned_delta`` per
    kernel, warm-cache zero measurements, and cold/warm config
    agreement (``autotune_ok`` rolls them up)."""
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_kernels.py",
    )
    timeout = 240
    if budget_s is not None:
        timeout = max(30, min(timeout, int(budget_s)))
    out = subprocess.run(
        [sys.executable, script, "--tuned",
         "--budget-s", str(max(10, timeout - 10))],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE or ""},
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_kernels --tuned failed (delta or warm-cache "
            f"gate): {out.stderr[-2000:] or out.stdout[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_observability(iters=300, windows=5) -> dict:
    """Overhead of the observability substrate on the two hot paths.

    Predict: the serving hot path's per-request instrumentation
    (admission counter, latency reservoir, span start/end) around a
    small-net ``output``, measured three ways — uninstrumented
    baseline, instrumented with ENABLED registry+tracer, instrumented
    with everything in no-op mode (disabled registry / disabled
    tracer). Train: ``fit_minibatch`` with and without a
    ``TelemetryListener`` (which also flips the engine's in-jit
    grad-norm output — that compiled-in cost is part of what's being
    measured). The acceptance gate is the no-op overheads <= 5%
    (within noise); enabled-mode numbers are reported alongside.
    """
    import jax

    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry
    from deeplearning4j_tpu.observability.runtime import (
        TelemetryListener,
    )
    from deeplearning4j_tpu.observability.trace import Tracer
    from deeplearning4j_tpu.serving.metrics import ServingMetrics

    def build_net():
        conf = (
            NeuralNetConfiguration.Builder().seed(7).learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_in=64, n_out=64, activation="tanh"))
            .layer(OutputLayer(n_out=10))
            .build()
        )
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(7)
    x = rng.randn(8, 64).astype(np.float32)
    y = np.eye(10)[rng.randint(0, 10, 8)].astype(np.float32)

    # -- predict path ---------------------------------------------------
    net = build_net()
    jax.block_until_ready(net.output(x))  # compile outside the window

    def predict_window(metrics, tracer):
        t0 = time.perf_counter()
        for _ in range(iters):
            if metrics is not None:
                metrics.try_enter(1 << 30)
                span = tracer.start_span("serving.request")
                s0 = time.monotonic()
            out = net.output(x)
            if metrics is not None:
                metrics.record_latency(time.monotonic() - s0)
                metrics.incr("predictions_total")
                span.end()
                metrics.exit()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6  # us/predict

    # interleave the three modes per window (baseline, enabled,
    # no-op) so slow drift (thermal, background load) hits all three
    # equally instead of whichever mode ran last; best-of per mode
    predict_modes = {
        "baseline": (None, None),
        "enabled": (ServingMetrics(), Tracer(seed=7)),
        "noop": (
            ServingMetrics(registry=MetricsRegistry(enabled=False)),
            Tracer(enabled=False),
        ),
    }
    mode_keys = list(predict_modes)
    predict_us = {k: float("inf") for k in predict_modes}
    for w in range(windows):
        for key in mode_keys[w % 3:] + mode_keys[:w % 3]:  # rotate
            metrics, tracer = predict_modes[key]
            predict_us[key] = min(
                predict_us[key], predict_window(metrics, tracer)
            )

    # -- train path -----------------------------------------------------
    ds = DataSet(features=x, labels=y)

    def make_train_net(listener):
        net_t = build_net()
        if listener is not None:
            net_t.listeners.append(listener)
        # two warmups: the FIRST iteration_done flips the engine's
        # telemetry step mode, so the telemetry-variant jit compiles
        # on the SECOND call — both stay outside the timed windows
        net_t.fit_minibatch(ds)
        net_t.fit_minibatch(ds)
        return net_t

    def train_window(net_t):
        t0 = time.perf_counter()
        for _ in range(iters):
            score = net_t.fit_minibatch(ds)
        float(score)  # sync
        return (time.perf_counter() - t0) / iters * 1e6  # us/step

    train_nets = {
        "baseline": make_train_net(None),
        "enabled": make_train_net(TelemetryListener(
            registry=MetricsRegistry(), frequency=iters,
            publish_memory=False,
        )),
        "noop": make_train_net(TelemetryListener(
            registry=MetricsRegistry(enabled=False),
            frequency=iters, publish_memory=False,
        )),
    }
    train_keys = list(train_nets)
    train_us = {k: float("inf") for k in train_nets}
    for w in range(windows):
        for key in train_keys[w % 3:] + train_keys[:w % 3]:  # rotate
            train_us[key] = min(
                train_us[key], train_window(train_nets[key])
            )

    def overhead(instrumented, baseline):
        return round(instrumented / baseline - 1.0, 4)

    return {
        "predict": {
            "baseline_us": round(predict_us["baseline"], 2),
            "enabled_us": round(predict_us["enabled"], 2),
            "noop_us": round(predict_us["noop"], 2),
            "enabled_overhead": overhead(
                predict_us["enabled"], predict_us["baseline"]),
            "noop_overhead": overhead(
                predict_us["noop"], predict_us["baseline"]),
        },
        "train": {
            "baseline_us": round(train_us["baseline"], 2),
            "enabled_us": round(train_us["enabled"], 2),
            "noop_us": round(train_us["noop"], 2),
            "enabled_overhead": overhead(
                train_us["enabled"], train_us["baseline"]),
            "noop_overhead": overhead(
                train_us["noop"], train_us["baseline"]),
        },
        "gate": "noop_overhead <= 0.05 on both paths (within noise)",
    }


def bench_profiler_overhead(iters=300, windows=5) -> dict:
    """Overhead of the hardware-truth step profiler + flight recorder
    on the training hot path, measured three ways on the same
    small-net ``fit_minibatch``: no profiler installed (baseline —
    the seams pay one global read + None check), a disabled
    ``StepProfiler`` installed (noop — one enabled-flag branch per
    hook), and the full enabled profiler with a ``FlightRecorder``
    ring attached. Budget gates: enabled <= 5%, noop <= 1%.
    """
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.observability import flightrec, profiler
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry

    # a step in the low-ms range — the floor for any real model;
    # sub-ms toy steps put the fixed ~tens-of-us bookkeeping above
    # any percentage gate by construction
    conf = (
        NeuralNetConfiguration.Builder().seed(7).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=128, n_out=256, activation="tanh"))
        .layer(DenseLayer(n_out=256, activation="tanh"))
        .layer(OutputLayer(n_out=10))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(7)
    ds = DataSet(
        features=rng.randn(32, 128).astype(np.float32),
        labels=np.eye(10)[rng.randint(0, 10, 32)].astype(np.float32),
    )
    net.fit_minibatch(ds)  # compile outside every window

    reg = MetricsRegistry()
    modes = {
        "baseline": None,
        "enabled": profiler.StepProfiler(
            registry=reg,
            recorder=flightrec.FlightRecorder(capacity=256,
                                              registry=reg),
        ),
        "noop": profiler.StepProfiler(registry=MetricsRegistry(),
                                      enabled=False),
    }
    # warm the enabled profiler's lazy cost model (one lowering per
    # shape/kind key) outside the timed windows
    prev = profiler.set_active_profiler(modes["enabled"])
    net.fit_minibatch(ds)
    profiler.set_active_profiler(prev)

    def window(prof):
        import gc

        gc.collect()  # enabled-mode garbage must not bill the others
        prev = profiler.set_active_profiler(prof)
        try:
            t0 = time.perf_counter()
            for _ in range(iters):
                score = net.fit_minibatch(ds)
            float(score)  # sync
            return (time.perf_counter() - t0) / iters * 1e6
        finally:
            profiler.set_active_profiler(prev)

    keys = list(modes)
    us = {k: float("inf") for k in modes}
    for w in range(windows):
        for key in keys[w % 3:] + keys[:w % 3]:  # rotate
            us[key] = min(us[key], window(modes[key]))

    def overhead(instrumented, baseline):
        return round(instrumented / baseline - 1.0, 4)

    return {
        "baseline_us": round(us["baseline"], 2),
        "enabled_us": round(us["enabled"], 2),
        "noop_us": round(us["noop"], 2),
        "enabled_overhead": overhead(us["enabled"], us["baseline"]),
        "noop_overhead": overhead(us["noop"], us["baseline"]),
        "ring_records": len(modes["enabled"].recorder.tail()),
        "gate": "enabled_overhead <= 0.05 and noop_overhead <= 0.01",
    }


# ---------------------------------------------------------------------------


# Default wall budget: the driver's kill timer matches the 870 s
# tier-1 budget; leave headroom for interpreter+jax startup, the
# final JSON, and the `timeout -k` grace window.
_DEFAULT_BUDGET_S = 600.0


class _BenchInterrupted(Exception):
    """SIGTERM/SIGALRM landed: stop the current section and emit the
    partial JSON instead of dying silently under ``timeout -k``."""


def _raise_interrupted(signum, frame):
    raise _BenchInterrupted(f"signal {signum}")


def _section_table(budget_fn):
    """(key, fn, unit) for every section. ``budget_fn()`` -> seconds
    left (None = unbounded) for the sections that shell out and must
    cap their own subprocess timeouts."""
    return [
        ("lenet_mnist", bench_lenet, "examples/sec/chip"),
        ("vgg16_cifar10", bench_vgg16, "examples/sec/chip"),
        ("lstm_char_rnn", bench_lstm_char_rnn, "chars/sec/chip"),
        ("lstm_saturated", bench_lstm_saturated, "chars/sec/chip"),
        ("word2vec_sg", bench_word2vec, "words/sec"),
        ("resnet50_imagenet", bench_resnet50, "examples/sec/chip"),
        ("transformer_lm", bench_transformer, "tokens/sec/chip"),
        ("dp_scaling", lambda: bench_dp_scaling(budget_s=budget_fn()),
         "dp sharding-overhead efficiency, fixed global batch "
         "(8 virtual cpu devices; 1.0 = zero overhead)"),
        ("elastic_recovery",
         lambda: bench_elastic_recovery(budget_fn()),
         "device-loss -> survivor-mesh recovery latency, kill half "
         "the 8-device virtual mesh mid-run (host-RAM snapshot "
         "ring; steps_lost < snapshot_every is the gate), plus "
         "preemption-notice -> emergency-checkpoint wall time"),
        ("host_recovery",
         lambda: bench_host_recovery(budget_fn()),
         "HOST-loss -> survivor re-formation latency: 2 real "
         "processes under the lease control plane, rank 1 "
         "SIGKILLed mid-run; plan-received -> trainer-rebuilt and "
         "-> first step on the re-formed mesh (steps_lost < "
         "snapshot_every is the gate)"),
        ("checkpoint_stall",
         lambda: bench_checkpoint_stall(budget_fn()),
         "training-thread stall per checkpoint save, write-behind vs "
         "sync on a ~70 MB model (async p99 stall <= 25% of the "
         "median sync save wall is the gate — the async stall is the "
         "host-snapshot copy alone)"),
        ("serving_microbatch",
         lambda: bench_serving(budget_fn()),
         "batched-vs-solo serving req/s at concurrency 32 "
         "(scripts/bench_serving.py; speedup >= 4 is the gate)"),
        ("serving_fleet",
         lambda: bench_serving_fleet(budget_fn()),
         "multi-tenant fleet: 4 router-fronted backend processes vs "
         "1, same total concurrency (scripts/bench_serving.py "
         "--fleet 4; scaling ~ process count on a multi-core host, "
         "zero post-warmup compiles fleet-wide)"),
        ("input_pipeline",
         lambda: bench_input_pipeline(budget_fn()),
         "pipelined-vs-synchronous training fit steps/sec "
         "(scripts/bench_training.py; speedup > 1 and "
         "trajectory_match are the gates)"),
        ("zero_sharding",
         lambda: bench_zero_sharding(budget_fn()),
         "ZeRO-sharded optimizer state + in-jit grad accumulation "
         "(scripts/bench_training.py --zero --grad-accum 4; bitwise "
         "trajectory_match and updater_bytes_ratio <= 0.25 are the "
         "gates)"),
        ("megastep",
         lambda: bench_megastep(budget_fn()),
         "megastep epochs: per-step fit vs K=6 steps fused into one "
         "dispatch behind the double-buffered chunk feed "
         "(scripts/bench_training.py --megastep 6; dispatches/step "
         "<= 1.5/K, input stall < 5% and bitwise trajectory_match "
         "are the gates)"),
        ("data_defense",
         lambda: bench_data_defense(budget_fn()),
         "bad-data defense clean-path A/B: validator + statistical "
         "anomaly guard off vs on (scripts/bench_training.py "
         "--defense; overhead <= 5%, zero clean quarantines and the "
         "no-trip bitwise lemmas are the gates)"),
        ("embeddings",
         lambda: bench_embeddings(budget_fn()),
         "mesh-row-sharded embedding tables: per-device residency "
         "~1/8 of replicated, deduped sparse row update vs dense "
         "[V, D]-cotangent step (bitwise match + speedup > 1), and "
         "fused sharded skip-gram/NS step loss parity "
         "(scripts/bench_embeddings.py; embeddings_ok rolls up the "
         "gates)"),
        ("aot_compile",
         lambda: bench_aot_compile(budget_fn()),
         "cold-vs-warm serving boot-to-ready "
         "(scripts/bench_compile.py; zero-compile warm restart "
         "and speedup_boot_aot > 1 are the gates)"),
        ("observability_overhead", bench_observability,
         "instrumented vs uninstrumented predict/train hot paths "
         "(no-op registry/tracer must be <= 5% overhead)"),
        ("profiler_overhead", bench_profiler_overhead,
         "step profiler + flight recorder vs uninstrumented "
         "fit_minibatch (enabled <= 5%, no profiler-installed "
         "noop <= 1% are the gates)"),
        ("compile_vs_depth",
         lambda: bench_compile_vs_depth(budget_fn()),
         "train-step trace+compile wall at transformer depth "
         "4/16/64, scan-over-layers off vs on "
         "(scripts/bench_transforms.py; >=2x at depth 64 is the "
         "gate)"),
        ("remat_memory",
         lambda: bench_remat_memory(budget_fn()),
         "activation working set + max-fitting batch at fixed "
         "budget, remat off vs on "
         "(scripts/bench_transforms.py; >=1.5x batch is the gate)"),
        ("fused_kernels",
         lambda: bench_fused_kernels(budget_fn()),
         "Pallas conv/matmul epilogue kernels vs XLA, interleaved "
         "A/B per config (scripts/bench_kernels.py; parity <= 1e-5 "
         "and compiled-op round-trip evidence are the gates; "
         "timing + MFU delta on real TPUs only)"),
        ("kernel_autotune",
         lambda: bench_kernel_autotune(budget_fn()),
         "measured tiling search vs divisor heuristic "
         "(scripts/bench_kernels.py --tuned; non-negative "
         "tuned_delta per kernel and a warm cached-mode pass with "
         "ZERO searches/measurements are the gates)"),
    ]


def _shape_entry(key, value, unit, peak) -> dict:
    """configs[key] payload from a section's raw result dict."""
    if set(value) == {"error"}:
        return value
    if "sharding_overhead_efficiency" in value:
        eff = value["sharding_overhead_efficiency"]
        return {"value": eff, "unit": unit, "vs_baseline": eff,
                "detail": value}
    if "value" not in value:
        # sectioned detail payloads (serving / input-pipeline A/Bs)
        return {"unit": unit, **value}
    value = dict(value)
    rate = value.pop("value")
    entry = {
        "value": round(rate, 1), "unit": unit,
        "vs_baseline": round(rate / BASELINES[key], 3),
    }
    f_ex = value.pop("flops_per_example", None)
    if f_ex:
        achieved = rate * f_ex
        entry["flops_per_example"] = round(f_ex)
        entry["achieved_tflops"] = round(achieved / 1e12, 2)
        if peak:
            entry["mfu"] = round(achieved / peak, 4)
    entry.update(value)  # data source, input-pipeline metrics, ...
    return entry


def _child_main(key: str) -> None:
    """``bench.py --section KEY``: run ONE section in this process
    and print its raw result dict as one JSON line. The parent runs
    each section in such a child so a section stuck inside an
    uninterruptible XLA compile can be SIGKILLed at its time box
    without taking the final JSON down with it (SIGALRM/SIGTERM only
    fire between Python bytecodes — a minutes-long C call sails
    straight through them, which is how BENCH_r05 died at rc=124)."""
    budget = float(
        os.environ.get("BENCH_SECTION_BUDGET_S", "0") or 0
    )
    t0 = time.monotonic()

    def rem():
        if budget <= 0:
            return None
        return max(budget - (time.monotonic() - t0), 10.0)

    table = {k: fn for k, fn, _ in _section_table(rem)}
    if key not in table:
        print(json.dumps({"error": f"unknown section {key!r}"}))
        return
    # shared persistent compile cache + accounting: this child reads
    # executables its siblings (and previous runs) already compiled,
    # and reports exactly what it hit/missed/compiled so an r06-style
    # "every section timed out" run is diagnosable from the JSON
    try:
        from deeplearning4j_tpu.compile.persistent import (
            cache_stats,
            enable_persistent_cache,
        )

        enable_persistent_cache()
        stats_before = cache_stats()
    except Exception as e:
        print(f"compile-cache setup failed: {e!r}", file=sys.stderr)
        cache_stats = None  # noqa: F811 — accounting is best-effort
    # sidecar: a SIGKILLed (timed-out) child never prints its JSON,
    # which is exactly when its compile accounting matters most — so
    # a daemon thread checkpoints the stats delta to the file the
    # parent names, and the parent reads it post-mortem
    sidecar = os.environ.get("BENCH_COMPILE_STATS_FILE")
    if sidecar and cache_stats is not None:
        import threading

        def _dump_loop():
            while True:
                try:
                    now = cache_stats()
                    doc = {k: round(now[k] - stats_before[k], 3)
                           for k in now}
                    doc["partial"] = True
                    with open(sidecar + ".tmp", "w") as f:
                        json.dump(doc, f)
                    os.replace(sidecar + ".tmp", sidecar)
                except Exception:
                    pass
                time.sleep(2.0)

        threading.Thread(target=_dump_loop, daemon=True,
                         name="bench-compile-stats").start()
    try:
        value = table[key]()
    except Exception as e:  # the parent shapes/records this
        value = {"error": str(e)[:500]}
    if cache_stats is not None and isinstance(value, dict):
        after = cache_stats()
        value["compile_cache"] = {
            k: round(after[k] - stats_before[k], 3)
            for k in after
        }
    if isinstance(value, dict):
        # the parent holds no jax (it would hold the chip its
        # children need): each child names the device it ran on
        from deeplearning4j_tpu.util.flops import device_peak_flops

        peak, kind = device_peak_flops()
        value["device"] = {"kind": kind, "peak_bf16_flops": peak}
    print(json.dumps(value), flush=True)


def main() -> None:
    if "--section" in sys.argv:  # child mode: one section, no boxing
        _child_main(sys.argv[sys.argv.index("--section") + 1])
        return

    # a chip belongs to one process: this parent starts a child per
    # section, so it never imports jax — the device's kind and peak
    # come from the first child's JSON (unboxed in-process runs, which
    # start no child, ask jax directly)
    peak, device_kind = None, None
    configs = {}
    # BENCH_BUDGET_S: wall budget for the whole run (default derived
    # from the ~870 s driver/tier-1 kill timer, minus startup and
    # final-JSON margin). Every section runs in a KILLABLE child
    # process under a fair-share time box, so the parent — which
    # does no jax work — always reaches the final JSON print and
    # exits 0 before the driver's `timeout -k` fires, whatever a
    # section does (BENCH_r05 rc=124 was an uninterruptible XLA
    # compile outliving SIGTERM's grace window in-process).
    # BENCH_BUDGET_S=0 disables the boxing and runs every section
    # in-process (the old path; use for unattended full runs).
    env_budget = os.environ.get("BENCH_BUDGET_S")
    budget_s = (
        float(env_budget) if env_budget not in (None, "")
        else _DEFAULT_BUDGET_S
    )
    t_start = time.monotonic()
    sections_skipped = []
    compile_stats = {}  # section key -> per-child cache hit/miss/seconds
    state = {"terminated": False, "child": None}

    def on_term(signum, frame):
        state["terminated"] = True
        child = state["child"]
        if child is not None:
            child.kill()
        raise _BenchInterrupted(f"signal {signum}")

    try:  # signals only bind on the main thread
        signal.signal(signal.SIGTERM, on_term)
        on_main = True
    except ValueError:
        on_main = False

    def remaining():
        if budget_s <= 0:
            return None
        return budget_s - (time.monotonic() - t_start)

    def run_child(key, cap) -> dict:
        import tempfile

        env = dict(os.environ)
        env["BENCH_SECTION_BUDGET_S"] = str(max(cap - 10.0, 15.0))
        # sidecar compile-stats file: survives a SIGKILL at the time
        # box, so even a timed-out section reports what it was
        # compiling (the r06 diagnosis this machinery exists for)
        fd, stats_file = tempfile.mkstemp(prefix="bench_cc_")
        os.close(fd)
        env["BENCH_COMPILE_STATS_FILE"] = stats_file

        def sidecar_stats():
            try:
                with open(stats_file) as f:
                    doc = json.load(f)
                return doc or None
            except Exception:
                return None
            finally:
                for p in (stats_file, stats_file + ".tmp"):
                    try:
                        os.unlink(p)
                    except OSError:
                        pass

        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--section", key],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env,
        )
        state["child"] = child
        try:
            out, err = child.communicate(timeout=cap)
        except subprocess.TimeoutExpired:
            child.kill()
            child.communicate()
            result = {"error": "timed out (section time box under "
                               "BENCH_BUDGET_S)"}
            cs = sidecar_stats()
            if cs:
                result["compile_cache"] = cs
            return result
        finally:
            state["child"] = None
        cs = sidecar_stats()  # also cleans the sidecar files up
        if child.returncode != 0:
            result = {"error": f"section exited "
                               f"rc={child.returncode}: {err[-400:]}"}
            if cs:
                result["compile_cache"] = cs
            return result
        try:
            return json.loads(out.strip().splitlines()[-1])
        except Exception:
            return {"error":
                    f"unparseable section output: {out[-200:]!r}"}

    sections = _section_table(remaining)
    # The final JSON is non-negotiable: whatever happens inside the
    # section loop (SIGTERM, a wedged child, an unexpected error),
    # the one-line result still prints and the process exits 0 with
    # whatever sections completed.
    try:
        if budget_s <= 0:
            # unboxed in-process run: account compiles around each
            # section with in-process stat deltas
            try:
                from deeplearning4j_tpu.compile.persistent import (
                    cache_stats,
                    enable_persistent_cache,
                )

                enable_persistent_cache()
            except Exception:
                cache_stats = None
            from deeplearning4j_tpu.util.flops import device_peak_flops

            peak, device_kind = device_peak_flops()
            for key, fn, unit in sections:
                before = cache_stats() if cache_stats else None
                try:
                    configs[key] = _shape_entry(key, fn(), unit, peak)
                except _BenchInterrupted:
                    raise
                except Exception as e:
                    configs[key] = {"error": str(e)[:500]}
                if before is not None:
                    after = cache_stats()
                    compile_stats[key] = {
                        k: round(after[k] - before[k], 3)
                        for k in after
                    }
        else:
            for i, (key, _fn, unit) in enumerate(sections):
                rem = remaining()
                if state["terminated"] or rem <= 15:
                    sections_skipped.append(key)
                    continue
                # fair-share time box: 1.5x this section's even share
                # of the remaining budget (finishing early donates
                # slack to later sections) — one slow section cannot
                # starve everything after it
                left = len(sections) - i
                cap = rem if left <= 1 else min(
                    rem, max(45.0, rem / left * 1.5)
                )
                value = run_child(key, cap)
                if "error" in value and "timed out" in value["error"]:
                    sections_skipped.append(key)
                cs = (value.pop("compile_cache", None)
                      if isinstance(value, dict) else None)
                if cs:
                    compile_stats[key] = cs
                dev = (value.pop("device", None)
                       if isinstance(value, dict) else None)
                if dev and device_kind is None:
                    device_kind = dev["kind"]
                    peak = dev["peak_bf16_flops"]
                configs[key] = _shape_entry(key, value, unit, peak)
    except _BenchInterrupted:  # SIGTERM: finish the JSON now
        pass
    except BaseException as e:  # noqa: BLE001 — JSON > stack trace
        configs.setdefault(
            "run_error", {"error": f"{type(e).__name__}: {e}"[:500]}
        )
    finally:
        if on_main:  # don't let a late signal corrupt the JSON line
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
        done = set(configs) | set(sections_skipped)
        sections_skipped.extend(
            k for k, _, _ in sections if k not in done
        )
        primary = configs.get("lenet_mnist", {})

        def _cc_total(field):
            return round(sum(
                s.get(field, 0) for s in compile_stats.values()
            ), 3)

        print(json.dumps({
            "metric": "lenet_mnist_fit_examples_per_sec",
            "value": primary.get("value"),
            "unit": "examples/sec/chip",
            "vs_baseline": primary.get("vs_baseline"),
            "device": device_kind,
            "peak_bf16_tflops": peak / 1e12 if peak else None,
            "budget_s": budget_s or None,
            "elapsed_s": round(time.monotonic() - t_start, 1),
            "sections_skipped": sections_skipped,
            # shared persistent-cache accounting: per-section compile
            # seconds make a blown budget attributable, and
            # hits vs misses make "the cache is warm" falsifiable
            "compile_cache": {
                "dir": _COMPILE_CACHE,
                "hits_total": _cc_total("hits"),
                "misses_total": _cc_total("misses"),
                "compile_seconds_total": _cc_total("compile_seconds"),
                "saved_seconds_total": _cc_total("saved_seconds"),
                "sections": compile_stats,
            },
            "configs": configs,
        }), flush=True)


if __name__ == "__main__":
    main()
