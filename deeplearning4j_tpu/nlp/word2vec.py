"""Word2Vec / SequenceVectors on batched XLA ops (reference:
``models/sequencevectors/SequenceVectors.java:161`` fit,
``models/word2vec/Word2Vec.java:31``, learning algorithms
``models/embeddings/learning/impl/elements/SkipGram.java:31`` /
``CBOW.java``, lookup table
``models/embeddings/inmemory/InMemoryLookupTable.java:55``).

TPU-first redesign of the hogwild trainer: the reference races N
threads over shared syn0/syn1 with per-pair axpy updates through the
native ``AggregateSkipGram`` op. Here the host packs fixed-shape
batches of (center, context, negatives | huffman path) int32 arrays
and ONE jitted XLA program does gather → dot → sigmoid → scatter-add
for the whole batch — the TPU-shaped equivalent of the fused native
aggregate. Updates within a batch are AVERAGED (synchronous
large-batch SGD; ``learning_rate`` is the batch-level step, default
0.5) rather than racing per pair; parity is statistical (SURVEY.md §7
hard part 3).
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.vocab import (
    Huffman,
    VocabCache,
    VocabConstructor,
    build_unigram_table,
    subsample_mask,
)

# ---------------------------------------------------------------------------
# Jitted update steps. Static over (B, K|L, D); shapes are pinned by
# the host batcher so each variant compiles once.
# ---------------------------------------------------------------------------


def _dense_rows() -> bool:
    """Historical knob, kept for signature/compile-cache stability: it
    used to route TPU lookups through a bf16 one-hot matmul (MXU-
    friendly gradient), but that materialized a ``[B, V]`` one-hot and
    rounded rows through bf16 — ``_rows`` is a plain gather on every
    platform now, bitwise-identical across this flag. The value still
    threads into the jitted steps as a static argument (so flipping
    ``DL4J_TPU_W2V_DENSE`` still re-keys the compile cache exactly as
    before), and sparse-gradient row updates live in
    ``embeddings/sparse.py``. Env override: DL4J_TPU_W2V_DENSE=1/0."""
    import os

    from deeplearning4j_tpu.ops.dispatch import effective_platform

    env = os.environ.get("DL4J_TPU_W2V_DENSE", "auto").lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off"):
        return False
    return effective_platform() == "tpu"


def _rows(table, ids, dense):
    """table[ids] — always a gather, on every platform.

    The ``dense=True`` branch used to lower this as
    ``one_hot(ids, V, bf16) @ table``: that materializes a ``[B, V]``
    one-hot (cost scales with VOCAB, not batch — the exact failure
    mode the sharded embeddings subsystem exists to avoid) and rounds
    the looked-up rows through bf16, so the two paths diverged by
    ~1e-4. ``jnp.take`` keeps the lookup O(B·D) and bitwise-identical
    whichever way ``dense`` is flipped; the MXU-gradient question is
    the sparse update's job now (``embeddings/sparse.py``).

    ``dense`` is REQUIRED and must be threaded in as a STATIC jit
    argument by the callers — it no longer changes the math (tests
    assert bitwise-equal loss across it), but it stays in every step
    signature so compile-cache keys and the ``DL4J_TPU_W2V_DENSE``
    override surface are unchanged."""
    del dense
    return jnp.take(table, ids, axis=0)


def _ns_step_raw(syn0, syn1neg, centers, contexts, negs, mask, alpha,
                 dense):
    """Negative-sampling step (SkipGram: centers=input word ids,
    contexts=predicted word ids; CBOW passes precomputed context means
    through ``_ns_step_cbow`` instead)."""
    def loss_fn(tables):
        s0, s1 = tables
        v = _rows(s0, centers, dense)        # [B, D]
        u_pos = _rows(s1, contexts, dense)   # [B, D]
        u_neg = _rows(s1, negs, dense)       # [B, K, D]
        pos = jax.nn.log_sigmoid(jnp.sum(v * u_pos, axis=-1))
        # a drawn negative equal to the true context is masked out (the
        # reference resamples on collision; masking is the static-shape
        # equivalent)
        nvalid = (negs != contexts[:, None]).astype(v.dtype)
        neg = jnp.sum(
            nvalid
            * jax.nn.log_sigmoid(-jnp.einsum("bd,bkd->bk", v, u_neg)),
            axis=-1,
        )
        return -jnp.sum(mask * (pos + neg)) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, (g0, g1) = jax.value_and_grad(loss_fn)((syn0, syn1neg))
    return syn0 - alpha * g0, syn1neg - alpha * g1, loss


def _hs_step_raw(syn0, syn1, centers, codes, points, path_mask, mask,
                 alpha, dense):
    """Hierarchical-softmax step: codes/points are the context word's
    padded Huffman path ([B, L]); loss per node is
    -log σ((1-2·code)·(v_center · syn1[point]))."""
    def loss_fn(tables):
        s0, s1 = tables
        v = _rows(s0, centers, dense)        # [B, D]
        u = _rows(s1, points, dense)         # [B, L, D]
        x = jnp.einsum("bd,bld->bl", v, u)
        sign = 1.0 - 2.0 * codes
        ll = jax.nn.log_sigmoid(sign * x) * path_mask
        return -jnp.sum(mask * jnp.sum(ll, axis=-1)) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, (g0, g1) = jax.value_and_grad(loss_fn)((syn0, syn1))
    return syn0 - alpha * g0, syn1 - alpha * g1, loss


# ``dense`` is a STATIC argument so the env-var/platform choice
# participates in the compilation cache key (flipping it recompiles
# instead of silently reusing the other path's executable).
_ns_step = functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("dense",)
)(_ns_step_raw)
_hs_step = functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("dense",)
)(_hs_step_raw)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("dense",))
def _sg_scan_steps(syn0, syn1, syn1neg, centers_k, contexts_k, codes_k,
                   points_k, pmask_k, negs_k, mask_k, alphas_k,
                   dense):
    """k skip-gram batches fused into ONE dispatch via lax.scan (same
    rationale as MultiLayerNetwork._build_multi_step: per-batch
    host->device transfers+dispatches bound throughput). hs/ns legs
    participate according to which table carries are non-None."""

    def body(tables, per):
        s0, s1, s1n = tables
        c, o, cd, pt, pm, ng, m, a = per
        loss = 0.0
        if s1 is not None:
            s0, s1, l1 = _hs_step_raw(s0, s1, c, cd, pt, pm, m, a,
                                      dense)
            loss = loss + l1
        if s1n is not None:
            s0, s1n, l2 = _ns_step_raw(s0, s1n, c, o, ng, m, a, dense)
            loss = loss + l2
        return (s0, s1, s1n), loss

    (syn0, syn1, syn1neg), losses = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (centers_k, contexts_k, codes_k, points_k, pmask_k, negs_k,
         mask_k, alphas_k),
    )
    return syn0, syn1, syn1neg, losses


_NEG_POOL_MAX = 1 << 18  # presampled negatives; rolled+tiled per epoch


@functools.partial(
    jax.jit, static_argnames=("N", "V", "P", "W", "K", "B"),
)
def _unpack_corpus(packed, *, N, V, P, W, K, B):
    """Split the single packed u16 upload back into corpus arrays
    (layout: ids[N] | pos|slen<<8 [N] | kp_q[V] | pool[P]). One
    buffer = ONE host->device transfer: each separate jnp.asarray pays
    its own round trip, and the corpus used to ship as 6 arrays."""
    ids = packed[:N].astype(jnp.int32)
    ps = packed[N:2 * N].astype(jnp.int32)
    pos = ps & 0xFF
    slen = ps >> 8
    kp = packed[2 * N:2 * N + V].astype(jnp.float32) / 65535.0
    pool = packed[2 * N + V:2 * N + V + P]
    # per-position keep prob: one [N] gather, ONCE per corpus — fine
    # outside the hot epoch loop (a one-hot matmul here would build
    # an [N, V] f32 intermediate: 1.7 GB at bench scale, HBM death
    # at real vocabularies)
    kp_pos = jnp.take(kp, ids, axis=0)
    return ids, pos, slen, kp_pos, pool


@functools.partial(
    jax.jit, donate_argnums=(0, 1),
    static_argnames=("E", "W", "K", "B", "dense"),
)
def _sg_device_epochs(syn0, syn1neg, ids, pos, slen, kp_pos, neg_pool,
                      base_key, sched, *, E, W, K, B, dense):
    """ONE dispatch = E full skip-gram/NS epochs, generated and
    trained on device (VERDICT r4 #2: the cold path was bounded by
    host pair-generation + host->device transfer of ~90 bytes/word;
    here the corpus ids live in HBM and each epoch's subsampling,
    reduced windows, negatives and updates are all device work — the
    TPU-shaped equivalent of the reference's producer thread
    (``SequenceVectors.java:935`` AsyncSequencer), which exists to
    hide exactly this host prep). An outer ``lax.scan`` over E epochs
    keeps the WHOLE multi-epoch fit in one dispatch (what per-epoch
    dispatching costs on the chip is not measured on the current
    code). Per-epoch keys fold in ON device and the
    linear alpha schedule derives from the 4-scalar ``sched``
    (lr0, lr_min, total_items, step0), so a fit's recurring host
    traffic is that one tiny array.

    Formulation: per-CENTER padded contexts. Each corpus position is a
    center with up to 2W context slots (validity mask = reduced
    window + sentence bounds + subsampling), and negatives are drawn
    per center, shared across its pairs. The loss is the exact pair
    sum Σ_pairs [log σ(v_c·u_o) + Σ_k log σ(-v_c·u_nk)] with the
    negative term factored per center (weighted by its surviving pair
    count, collision-masked per pair) — word2vec.c semantics up to
    negative-sample sharing, which trades per-pair draws for a ~3x
    FLOP cut in the dominant one-hot lookups (statistical parity,
    module docstring). Alphas come in precomputed per batch.

    Divergences from the host generator (documented): subsampling
    masks pairs in place rather than compacting the corpus first (so
    windows do not stretch across removed frequent words), and
    negatives come from a host-presampled unigram^0.75 pool rotated by
    a random per-epoch offset rather than fresh per-epoch table draws
    — the marginal distribution is identical (the pool is itself
    table-sampled), only cross-epoch independence is relaxed.

    The generation phase is deliberately GATHER-FREE: contexts and
    keep-flags are built by 2W static shifts of the corpus array,
    per-position keep probabilities and the negative pool come in
    precomputed — TPUs execute large scalar gathers row-serially, and
    a gather-based first cut of this generator cost more than the
    training matmuls it feeds.
    """
    N = ids.shape[0]
    n_batches = N // B
    ids32 = ids.astype(jnp.int32)
    offsets = [o for o in range(-W, W + 1) if o != 0]
    offs = jnp.asarray(offsets, jnp.int32)
    p = pos[:, None] + offs[None, :]
    inb = (p >= 0) & (p < slen[:, None])
    pad_ids = jnp.pad(ids32, (W, W))
    # context ids via static shifts, not gathers (epoch-independent)
    ctx = jnp.stack(
        [pad_ids[W + o:W + o + N] for o in offsets], axis=1
    )                                                   # [N, 2W]
    centers_b = ids32[: n_batches * B].reshape(n_batches, B)
    ctx_b = ctx[: n_batches * B].reshape(n_batches, B, -1)

    def body(tables, per):
        s0, s1n = tables
        c, cx, cm, ng, a = per

        def loss_fn(ts):
            t0, t1 = ts
            v = _rows(t0, c, dense)                     # [B, D]
            u_c = _rows(t1, cx, dense)                  # [B, 2W, D]
            u_n = _rows(t1, ng, dense)                  # [B, K, D]
            pos_ll = jax.nn.log_sigmoid(
                jnp.einsum("bd,bwd->bw", v, u_c)
            )
            # per-pair collision mask (reference resamples a negative
            # equal to the true context; masking is the static-shape
            # equivalent): weight of negative k = count of this
            # center's valid pairs whose context != negs[k]
            w_k = jnp.einsum(
                "bw,bkw->bk", cm,
                (ng[:, :, None] != cx[:, None, :]).astype(cm.dtype),
            )
            neg_ll = jax.nn.log_sigmoid(
                -jnp.einsum("bd,bkd->bk", v, u_n)
            )
            npairs = jnp.maximum(jnp.sum(cm), 1.0)
            return -(jnp.sum(cm * pos_ll)
                     + jnp.sum(w_k * neg_ll)) / npairs

        loss, (g0, g1) = jax.value_and_grad(loss_fn)((s0, s1n))
        return (s0 - a * g0, s1n - a * g1), loss

    lr0, lr_min, total, step0 = (sched[0], sched[1], sched[2],
                                 sched[3])

    def epoch(tables, e):
        key = jax.random.fold_in(base_key, e)
        steps = (step0 + e.astype(jnp.float32) * n_batches
                 + jnp.arange(n_batches, dtype=jnp.float32))
        frac = jnp.minimum(steps * B / total, 1.0)
        alphas_e = jnp.maximum(lr0 * (1.0 - frac), lr_min)
        k1, k2, k3 = jax.random.split(key, 3)
        keep = jax.random.uniform(k1, (N,)) < kp_pos
        b = jax.random.randint(k2, (N,), 1, W + 1)
        pad_keep = jnp.pad(keep, (W, W))
        keep_ctx = jnp.stack(
            [pad_keep[W + o:W + o + N] for o in offsets], axis=1
        )
        cmask = (
            inb
            & (jnp.abs(offs)[None, :] <= b[:, None])
            & keep[:, None] & keep_ctx
        ).astype(syn0.dtype)
        shift = jax.random.randint(k3, (), 0, neg_pool.size)
        flat = jnp.roll(neg_pool.reshape(-1), shift)
        reps = -(-(N * K) // flat.size)
        if reps > 1:
            flat = jnp.tile(flat, reps)
        negs = flat[: N * K].reshape(N, K).astype(jnp.int32)
        per = (
            centers_b,
            ctx_b,
            cmask[: n_batches * B].reshape(n_batches, B, -1),
            negs[: n_batches * B].reshape(n_batches, B, -1),
            alphas_e,
        )
        tables, losses = jax.lax.scan(body, tables, per)
        return tables, losses

    (syn0, syn1neg), losses = jax.lax.scan(
        epoch, (syn0, syn1neg), jnp.arange(E, dtype=jnp.int32)
    )
    return syn0, syn1neg, losses


def _cbow_hidden(s0, ctx_ids, ctx_mask, dense):
    ctx = _rows(s0, ctx_ids, dense)          # [B, W, D]
    denom = jnp.maximum(jnp.sum(ctx_mask, axis=-1, keepdims=True), 1.0)
    return jnp.sum(ctx * ctx_mask[..., None], axis=1) / denom  # [B, D]


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("dense",))
def _cbow_ns_step(syn0, syn1neg, ctx_ids, ctx_mask, targets, negs, mask,
                  alpha, dense):
    """CBOW + negative sampling: mean of context vectors predicts the
    center word (reference ``CBOW.java`` iterateSample)."""
    def loss_fn(tables):
        s0, s1 = tables
        h = _cbow_hidden(s0, ctx_ids, ctx_mask, dense)
        u_pos = _rows(s1, targets, dense)
        u_neg = _rows(s1, negs, dense)
        pos = jax.nn.log_sigmoid(jnp.sum(h * u_pos, axis=-1))
        nvalid = (negs != targets[:, None]).astype(h.dtype)
        neg = jnp.sum(
            nvalid
            * jax.nn.log_sigmoid(-jnp.einsum("bd,bkd->bk", h, u_neg)),
            axis=-1,
        )
        return -jnp.sum(mask * (pos + neg)) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, (g0, g1) = jax.value_and_grad(loss_fn)((syn0, syn1neg))
    return syn0 - alpha * g0, syn1neg - alpha * g1, loss


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("dense",))
def _cbow_hs_step(syn0, syn1, ctx_ids, ctx_mask, codes, points, path_mask,
                  mask, alpha, dense):
    """CBOW + hierarchical softmax: context mean against the TARGET
    word's Huffman path."""
    def loss_fn(tables):
        s0, s1 = tables
        h = _cbow_hidden(s0, ctx_ids, ctx_mask, dense)
        u = _rows(s1, points, dense)         # [B, L, D]
        x = jnp.einsum("bd,bld->bl", h, u)
        sign = 1.0 - 2.0 * codes
        ll = jax.nn.log_sigmoid(sign * x) * path_mask
        return -jnp.sum(mask * jnp.sum(ll, axis=-1)) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, (g0, g1) = jax.value_and_grad(loss_fn)((syn0, syn1))
    return syn0 - alpha * g0, syn1 - alpha * g1, loss


# ---------------------------------------------------------------------------
# Lookup table
# ---------------------------------------------------------------------------


class InMemoryLookupTable:
    """syn0/syn1/syn1neg embedding matrices (reference
    ``InMemoryLookupTable.java:55``); syn0 rows are the word vectors."""

    def __init__(self, cache: VocabCache, layer_size: int, seed: int = 12345,
                 use_hs: bool = False, negative: int = 5):
        self.cache = cache
        self.layer_size = layer_size
        self.use_hs = use_hs
        self.negative = negative
        v = len(cache)
        rng = np.random.RandomState(seed)
        # reference resetWeights: syn0 ~ U(-0.5, 0.5)/layerSize
        self.syn0 = jnp.asarray(
            (rng.rand(v, layer_size) - 0.5) / layer_size, jnp.float32
        )
        self.syn1 = (
            jnp.zeros((v, layer_size), jnp.float32) if use_hs else None
        )
        self.syn1neg = (
            jnp.zeros((v, layer_size), jnp.float32) if negative > 0 else None
        )
        self._normalized: Optional[np.ndarray] = None

    def vector(self, word: str) -> Optional[np.ndarray]:
        i = self.cache.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def invalidate_norms(self):
        self._normalized = None

    def normalized(self) -> np.ndarray:
        if self._normalized is None:
            m = np.asarray(self.syn0)
            norms = np.linalg.norm(m, axis=1, keepdims=True)
            self._normalized = m / np.maximum(norms, 1e-12)
        return self._normalized


# ---------------------------------------------------------------------------
# SequenceVectors: generic trainer over id sequences
# ---------------------------------------------------------------------------


class SequenceVectors:
    """Generic embedding trainer over integer id sequences (reference
    ``SequenceVectors<T>`` — DeepWalk and ParagraphVectors reuse it).

    Subclasses/owners supply: a built ``VocabCache`` and an iterable of
    id sequences per epoch (``_sequences()``).
    """

    def __init__(self, cache: VocabCache, *, layer_size=100, window=5,
                 learning_rate=0.5, min_learning_rate=1e-4, negative=5,
                 use_hierarchic_softmax=False, sample=1e-3, epochs=1,
                 iterations=1, batch_size=1024, seed=12345,
                 algorithm="SkipGram"):
        if negative <= 0 and not use_hierarchic_softmax:
            raise ValueError(
                "Need negative sampling (negative>0) or hierarchical "
                "softmax (use_hierarchic_softmax=True)"
            )
        self.cache = cache
        self.layer_size = layer_size
        self.window = window
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.sample = sample
        self.epochs = epochs
        self.iterations = iterations
        self.batch_size = batch_size
        self.seed = seed
        self.algorithm = algorithm
        self.scan_chunk = 16  # skip-gram batches fused per dispatch
        # Device-resident epoch replay: the prepared (ids, negatives,
        # masks, alphas) chunk arrays for an epoch are cached in HBM
        # keyed by (epoch seed, step offset, batch/scan geometry, and
        # every hyperparameter baked into the arrays), so repeated
        # fits (and epochs>1 re-runs with matching keys) skip ALL
        # host-side pair generation + transfer — the NLP analog of the
        # engines' multi-epoch device cache. Pure caching: the cached
        # arrays are bit-identical to regeneration (same seeds); a
        # subclass that mutates its corpus between fits under the same
        # seed must call clear_epoch_cache(). Bounded by
        # ``epoch_cache_budget_bytes`` (epochs past the budget stream
        # as before); 0 disables like cache_epoch_data=False.
        self.cache_epoch_data = True
        self.epoch_cache_budget_bytes = 256 * 2 ** 20
        self._epoch_cache: dict = {}
        self._epoch_cache_bytes = 0
        # On-device epoch generation (skip-gram/NS only): "auto" =
        # enabled on TPU, where the cold path is otherwise bounded by
        # host pair-gen + transfer; True/False force. Env override:
        # DL4J_TPU_W2V_DEVICE_GEN=1/0.
        self.device_epoch_gen = "auto"
        self._dev_base_key = None
        self._dev_corpus = None  # (key, (ids, pos, slen, kp_pos, pool, n))
        # device-gen continuation counters: repeated fit() calls must
        # draw FRESH epoch keys (the first fit's stream replayed
        # verbatim before) and continue the lr schedule where the
        # last fit stopped instead of restarting it
        self._dev_fit_no = 0
        self._dev_steps_done = 0
        self.lookup = self._make_lookup()
        self._rng = np.random.RandomState(seed)
        if use_hierarchic_softmax:
            huff = Huffman(cache.words)
            huff.build()
            self._codes, self._points, self._code_lens = huff.padded_arrays()
        if negative > 0:
            self._table = build_unigram_table(cache)
        self._counts = np.array([w.count for w in cache.words], np.int64)

    def _make_lookup(self) -> InMemoryLookupTable:
        """Lookup-table factory hook: the mesh-sharded subclass
        (``embeddings/word2vec.py``) substitutes row-sharded tables
        here, so the dense ``[V, D]`` device arrays never allocate for
        vocabularies that don't fit one device."""
        return InMemoryLookupTable(
            self.cache, self.layer_size, seed=self.seed,
            use_hs=self.use_hs, negative=self.negative,
        )

    # -- corpus plumbing ----------------------------------------------------

    def _sequences(self) -> Iterable[np.ndarray]:
        raise NotImplementedError

    def _flatten_corpus(self, rng):
        """Concatenate every sequence into corpus-wide arrays for
        vectorized window generation: (all_ids, pos-in-sentence,
        own-sentence-length, reduced-window draw b ~ U{1..window}) —
        after frequent-word subsampling. Returns None for an
        empty/too-short corpus. Shared by the SkipGram and CBOW pair
        generators (the per-sentence Python loop this replaces
        dominated fit() wall-clock)."""
        total = self.cache.total_word_count
        seqs = [np.asarray(ids, np.int32) for ids in self._sequences()]
        seqs = [s for s in seqs if len(s) > 0]
        if not seqs:
            return None
        all_ids = np.concatenate(seqs)
        lens = np.array([len(s) for s in seqs], np.int32)
        sent = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
        if self.sample > 0:
            keep = subsample_mask(
                all_ids, self._counts, total, self.sample, rng
            )
            all_ids = all_ids[keep]
            sent = sent[keep]
            lens = np.bincount(sent, minlength=len(lens)).astype(np.int32)
        n = len(all_ids)
        if n < 2:
            return None
        starts = np.repeat(
            np.cumsum(lens, dtype=np.int64).astype(np.int32) - lens, lens
        )
        pos = np.arange(n, dtype=np.int32) - starts
        slen = np.repeat(lens, lens)
        b = rng.randint(1, self.window + 1, n)
        return all_ids, pos, slen, b

    def _gen_pairs(self, epoch_seed: int):
        """(centers, contexts) int32 arrays for one epoch: reduced
        window sampling + frequent-word subsampling (reference
        SkipGram.learnSequence).

        Vectorized over the WHOLE corpus, not per sentence: all
        sequences are concatenated with a sentence-id array, so pair
        generation is ~2*window numpy slices total instead of per
        sentence — the host-side analog of batching for the MXU (the
        per-sentence loop dominated fit() wall-clock before)."""
        rng = np.random.RandomState(epoch_seed)
        flat = self._flatten_corpus(rng)
        if flat is None:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        all_ids, pos, slen, b = flat
        centers: List[np.ndarray] = []
        contexts: List[np.ndarray] = []
        for off in range(1, self.window + 1):
            idx = np.nonzero(b >= off)[0]
            left = idx[pos[idx] >= off]
            centers.append(all_ids[left])
            contexts.append(all_ids[left - off])
            right = idx[pos[idx] < slen[idx] - off]
            centers.append(all_ids[right])
            contexts.append(all_ids[right + off])
        c = np.concatenate(centers).astype(np.int32)
        o = np.concatenate(contexts).astype(np.int32)
        perm = rng.permutation(len(c))
        return c[perm], o[perm]

    def _gen_cbow(self, epoch_seed: int):
        """(targets[N], ctx_ids[N, 2W], ctx_mask[N, 2W]) for one epoch
        (true windowed CBOW: all context words within the reduced
        window feed one averaged prediction)."""
        rng = np.random.RandomState(epoch_seed)
        W = self.window
        offsets = [o for o in range(-W, W + 1) if o != 0]
        flat = self._flatten_corpus(rng)
        if flat is None:
            z = np.zeros((0, 2 * W), np.int32)
            return np.zeros(0, np.int32), z, z.astype(np.float32)
        all_ids, pos, slen, b = flat
        n = len(all_ids)
        padded = np.pad(all_ids, (W, W))
        cols, masks = [], []
        for off in offsets:
            cols.append(padded[W + off:W + off + n])
            masks.append(
                (pos + off >= 0) & (pos + off < slen)
                & (np.abs(off) <= b)
            )
        ctx = np.stack(cols, 1).astype(np.int32)
        cm = np.stack(masks, 1)
        keep_rows = cm.any(axis=1)
        t = all_ids[keep_rows].astype(np.int32)
        c = ctx[keep_rows]
        m = cm[keep_rows].astype(np.float32)
        perm = rng.permutation(len(t))
        return t[perm], c[perm], m[perm]

    # -- training -----------------------------------------------------------

    def clear_epoch_cache(self) -> None:
        """Drop the device-resident epoch replay cache AND the
        device-generation corpus arrays (required after mutating the
        corpus without changing the seed)."""
        self._epoch_cache.clear()
        self._epoch_cache_bytes = 0
        self._dev_corpus = None

    def _epoch_cache_key(self, ep_seed: int, step: int):
        """Everything that shapes the prepared chunk arrays: epoch
        seed + step offset (negatives, alpha offsets), geometry, the
        hyperparameters baked into alphas/negatives/hs-paths, and the
        pair-generation knobs (window/sample/algorithm shape
        ``_gen_pairs`` output via ``_flatten_corpus``)."""
        return (
            ep_seed, step, self.batch_size, self.scan_chunk,
            self.learning_rate, self.min_learning_rate, self.epochs,
            self.negative, self.use_hs,
            self.window, self.sample, self.algorithm,
        )

    @staticmethod
    def _chunks_nbytes(chunks) -> int:
        total = 0
        for tup in chunks:
            for a in tup[:-1]:
                if a is not None:
                    total += int(np.prod(a.shape)) * a.dtype.itemsize
        return total

    def _use_device_gen(self) -> bool:
        import os

        from deeplearning4j_tpu.ops.dispatch import effective_platform

        if not (self.algorithm == "SkipGram" and self.negative > 0
                and not self.use_hs and self.iterations == 1
                and self._scan_path_ok()):
            return False
        env = os.environ.get("DL4J_TPU_W2V_DEVICE_GEN", "").lower()
        if env in ("1", "true", "on"):
            return True
        if env in ("0", "false", "off"):
            return False
        flag = self.device_epoch_gen
        if flag == "auto":
            return effective_platform() == "tpu"
        return bool(flag)

    def _flat_corpus_static(self):
        """One-time (ids, pos, slen) over the UNsubsampled corpus for
        the device-generation path — subsampling is drawn on device
        per epoch, so these arrays are epoch-independent."""
        seqs = [np.asarray(ids, np.int32) for ids in self._sequences()]
        seqs = [s for s in seqs if len(s) > 0]
        if not seqs:
            return None
        all_ids = np.concatenate(seqs)
        lens = np.array([len(s) for s in seqs], np.int32)
        starts = np.repeat(
            np.cumsum(lens, dtype=np.int64).astype(np.int32) - lens, lens
        )
        pos = np.arange(len(all_ids), dtype=np.int32) - starts
        slen = np.repeat(lens, lens)
        return all_ids, pos, slen

    def _keep_probs(self) -> np.ndarray:
        """Per-word P(keep) of frequent-word subsampling (reference
        SkipGram sample branch), as a [V] table for device draws."""
        v = len(self._counts)
        if self.sample <= 0:
            return np.ones(v, np.float32)
        total = max(self.cache.total_word_count, 1)
        freq = self._counts / total
        kp = (np.sqrt(freq / self.sample) + 1) * (
            self.sample / np.maximum(freq, 1e-12)
        )
        return np.minimum(kp, 1.0).astype(np.float32)

    def _fit_device_gen(self) -> None:
        """Epoch loop for the on-device generation path: one
        ``_sg_device_epoch`` dispatch per epoch; the only recurring
        host work is the [n_batches] alpha schedule."""
        B = self.batch_size
        # staleness key: everything baked into the cached device arrays
        # (kp_pos bakes sample; the pool bakes negative+seed; padding
        # bakes batch_size) — same discipline as _epoch_cache_key
        dev_key = (B, self.negative, self.sample, self.seed)
        if self._dev_corpus is not None and self._dev_corpus[0] != dev_key:
            self._dev_corpus = None
        if self._dev_corpus is None:
            flat = self._flat_corpus_static()
            if flat is None:
                return
            all_ids, pos, slen = flat
            n = len(all_ids)
            pad = (-n) % B
            if pad:
                all_ids = np.pad(all_ids, (0, pad))
                pos = np.pad(pos, (0, pad))
                slen = np.pad(slen, (0, pad))  # slen 0 -> no pairs
            V = len(self._counts)
            pool_rng = np.random.RandomState(self.seed ^ 0x5EED)
            P = int(min(len(all_ids) * self.negative, _NEG_POOL_MAX))
            pool = self._table[
                pool_rng.randint(0, len(self._table), P)
            ]
            if V < 2 ** 16 and int(slen.max(initial=0)) < 256:
                # ONE u16 buffer = ONE transfer: ids | pos|slen<<8 |
                # kp quantized to u16 fixed point | negative pool.
                # Each separate jnp.asarray pays a full host->device
                # round trip — the cold fit was 6 round trips of
                # latency, not bandwidth.
                kp_q = np.round(
                    self._keep_probs() * 65535.0
                ).astype(np.uint16)
                packed = np.concatenate([
                    all_ids.astype(np.uint16),
                    (pos.astype(np.uint16)
                     | (slen.astype(np.uint16) << 8)),
                    kp_q,
                    pool.astype(np.uint16),
                ])
                self._dev_upload_bytes = packed.nbytes
                arrs = _unpack_corpus(
                    jnp.asarray(packed), N=len(all_ids), V=V, P=P,
                    W=self.window, K=self.negative, B=B,
                )
            else:
                # large-vocab / long-sentence fallback: plain arrays
                idt = np.uint16 if V < 2 ** 16 else np.int32
                kp_pos = self._keep_probs()[all_ids].astype(np.float32)
                arrs = (
                    jnp.asarray(all_ids.astype(idt)),
                    jnp.asarray(pos), jnp.asarray(slen),
                    jnp.asarray(kp_pos), jnp.asarray(pool.astype(idt)),
                )
                self._dev_upload_bytes = sum(
                    int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in arrs
                )
            self._dev_corpus = (dev_key, (*arrs, n))
        ids_d, pos_d, slen_d, kp_d, pool_d, n_words = self._dev_corpus[1]
        n_batches = ids_d.shape[0] // B
        E = self.epochs
        lr0, lr_min = self.learning_rate, self.min_learning_rate
        lk = self.lookup
        if self._dev_base_key is None:
            self._dev_base_key = jax.random.PRNGKey(self.seed)
        # Repeated fit() calls continue training, not replay it: the
        # fit counter folds into the base key so call #2 draws fresh
        # epoch keys (before this, the identical sampling stream
        # re-ran every call), and the lr schedule resumes from the
        # steps already taken. The first call folds nothing and sees
        # the original totals, so its trajectory stays bitwise
        # identical to prior releases.
        base_key = self._dev_base_key
        if self._dev_fit_no:
            base_key = jax.random.fold_in(base_key, self._dev_fit_no)
        total = max((self._dev_steps_done + n_batches * E) * B, 1)
        # ALL epochs in one dispatch; the schedule rides in as 4
        # scalars and per-epoch keys fold in on device, so a fit is
        # one tiny transfer + one dispatch (no per-epoch dispatch
        # and no per-epoch host-side fold_in round trip)
        sched = jnp.asarray(
            [lr0, lr_min, float(total), float(self._dev_steps_done)],
            jnp.float32,
        )
        lk.syn0, lk.syn1neg, _ = _sg_device_epochs(
            lk.syn0, lk.syn1neg, ids_d, pos_d, slen_d, kp_d,
            pool_d, base_key, sched,
            E=E, W=self.window, K=self.negative, B=B,
            dense=_dense_rows(),
        )
        self._dev_fit_no += 1
        self._dev_steps_done += n_batches * E
        lk.invalidate_norms()

    def fit(self) -> None:
        if self._use_device_gen():
            return self._fit_device_gen()
        B = self.batch_size
        lr0, lr_min = self.learning_rate, self.min_learning_rate
        total_items = None
        step = 0
        cbow = self.algorithm == "CBOW"
        for epoch in range(self.epochs):
            scan_ok = (
                not cbow and self.scan_chunk > 1
                and self.iterations == 1
                and self._scan_path_ok()
            )
            ep_seed = self.seed + 31 * epoch
            caching = (
                self.cache_epoch_data
                and self.epoch_cache_budget_bytes > 0
            )
            if scan_ok:
                key = self._epoch_cache_key(ep_seed, step)
                entry = self._epoch_cache.get(key) if caching else None
                if entry is not None:
                    n_items, chunks = entry
                    if total_items is None:
                        total_items = max(n_items * self.epochs, 1)
                    step = self._run_scan_chunks(chunks, step)
                    continue
            if cbow:
                t, c, m = self._gen_cbow(ep_seed)
                n_items = len(t)
            else:
                c, o = self._gen_pairs(ep_seed)
                n_items = len(c)
            if total_items is None:
                total_items = max(n_items * self.epochs, 1)
            if scan_ok:
                chunks = self._prepare_scan_chunks(
                    c, o, step, total_items, lr0, lr_min
                )
                if caching:
                    nbytes = self._chunks_nbytes(chunks)
                    if (self._epoch_cache_bytes + nbytes
                            <= self.epoch_cache_budget_bytes):
                        self._epoch_cache[key] = (n_items, chunks)
                        self._epoch_cache_bytes += nbytes
                step = self._run_scan_chunks(chunks, step)
                continue
            for s in range(0, n_items, B):
                mask = np.ones(B, np.float32)
                if cbow:
                    tb, cb, mb = t[s:s + B], c[s:s + B], m[s:s + B]
                    if len(tb) < B:
                        pad = B - len(tb)
                        mask[len(tb):] = 0.0
                        tb = np.pad(tb, (0, pad))
                        cb = np.pad(cb, ((0, pad), (0, 0)))
                        mb = np.pad(mb, ((0, pad), (0, 0)))
                else:
                    cb, ob = c[s:s + B], o[s:s + B]
                    if len(cb) < B:
                        pad = B - len(cb)
                        mask[len(cb):] = 0.0
                        cb = np.pad(cb, (0, pad))
                        ob = np.pad(ob, (0, pad))
                frac = min((step * B) / total_items, 1.0)
                alpha = max(lr0 * (1 - frac), lr_min)
                for _ in range(self.iterations):
                    if cbow:
                        self._apply_cbow_batch(tb, cb, mb, mask, alpha, step)
                    else:
                        self._apply_batch(cb, ob, mask, alpha, step)
                step += 1
        self.lookup.invalidate_norms()

    def _scan_path_ok(self) -> bool:
        """The scan epoch bypasses the per-batch ``_apply_batch`` hook;
        a subclass overriding it would silently lose its override, so
        scanning requires either the base hook or an explicit
        ``scan_path_compatible = True`` (set by subclasses that hook
        placement via ``_put_stacked`` instead)."""
        return (
            type(self)._apply_batch is SequenceVectors._apply_batch
            or getattr(self, "scan_path_compatible", False)
        )

    def _prepare_scan_chunks(self, centers, contexts, step, total_items,
                             lr0, lr_min) -> list:
        """Build the device-resident chunk arrays for one scan-fused
        skip-gram epoch: ``scan_chunk`` batches per XLA call, identical
        math/negative-sampling/alphas to the per-batch path (same
        per-batch step seeds). Returns a list of per-dispatch tuples
        consumed by :meth:`_run_scan_chunks` (and cached for epoch
        replay — ``_sg_scan_steps`` donates only the tables, never
        these batch arrays, so they are reusable)."""
        B = self.batch_size
        K = self.scan_chunk
        n = len(centers)
        # word ids transfer at native width (uint16 for vocabs under
        # 64k — half the host->device bytes); the on-device gather
        # accepts either and values are identical
        idt = np.uint16 if len(self._counts) < 2 ** 16 else np.int32
        chunks = []
        for s0 in range(0, n, B * K):
            cs = centers[s0:s0 + B * K]
            os_ = contexts[s0:s0 + B * K]
            k = (len(cs) + B - 1) // B
            pad = k * B - len(cs)
            mask = np.ones(k * B, np.float32)
            if pad:
                mask[len(cs):] = 0.0
                cs = np.pad(cs, (0, pad))
                os_ = np.pad(os_, (0, pad))
            ck = cs.reshape(k, B).astype(idt, copy=False)
            ok = os_.reshape(k, B).astype(idt, copy=False)
            mk = mask.reshape(k, B)
            alphas = np.empty(k, np.float32)
            negs = (
                np.empty((k, B, self.negative), idt)
                if self.negative > 0 else None
            )
            for i in range(k):
                frac = min(((step + i) * B) / total_items, 1.0)
                alphas[i] = max(lr0 * (1 - frac), lr_min)
                if negs is not None:
                    negs[i] = self._sample_negatives(B, step + i)
            if self.use_hs:
                codes, points, pmask = self._path_arrays(ok.ravel())
                ckd = jnp.asarray(codes).reshape(k, B, -1)
                ptd = jnp.asarray(points).reshape(k, B, -1)
                pmd = jnp.asarray(pmask).reshape(k, B, -1)
            else:
                ckd = ptd = pmd = None
            chunks.append((
                self._put_stacked(ck), self._put_stacked(ok),
                ckd, ptd, pmd,
                self._put_stacked(negs) if negs is not None else None,
                self._put_stacked(mk), jnp.asarray(alphas), k,
            ))
            step += k
        return chunks

    def _run_scan_chunks(self, chunks, step) -> int:
        """Run a prepared epoch: one fused-scan dispatch per chunk,
        zero host work (the device-resident replay path)."""
        lk = self.lookup
        for (ck, ok, ckd, ptd, pmd, negs, mk, alphas, k) in chunks:
            lk.syn0, lk.syn1, lk.syn1neg, _ = _sg_scan_steps(
                lk.syn0, lk.syn1, lk.syn1neg, ck, ok, ckd, ptd, pmd,
                negs, mk, alphas, dense=_dense_rows(),
            )
            step += k
        return step

    def _put_stacked(self, a):
        """Placement hook for [k, B, ...] stacked batch arrays (the
        mesh-sharded subclass shards the B axis)."""
        return jnp.asarray(a)

    def _path_arrays(self, word_ids: np.ndarray):
        codes = jnp.asarray(self._codes[word_ids])
        points = jnp.asarray(self._points[word_ids])
        lens = self._code_lens[word_ids]
        pmask = jnp.asarray(
            (np.arange(self._codes.shape[1])[None, :] < lens[:, None])
            .astype(np.float32)
        )
        return codes, points, pmask

    def _apply_batch(self, centers, contexts, mask, alpha, step):
        lk = self.lookup
        alpha = jnp.float32(alpha)
        mask = jnp.asarray(mask)
        cb = jnp.asarray(centers)
        ob = jnp.asarray(contexts)
        if self.use_hs:
            codes, points, pmask = self._path_arrays(contexts)
            lk.syn0, lk.syn1, _ = _hs_step(
                lk.syn0, lk.syn1, cb, codes, points, pmask, mask, alpha,
                dense=_dense_rows(),
            )
        if self.negative > 0:
            negs = self._sample_negatives(len(centers), step)
            lk.syn0, lk.syn1neg, _ = _ns_step(
                lk.syn0, lk.syn1neg, cb, ob, jnp.asarray(negs), mask, alpha,
                dense=_dense_rows(),
            )

    def _apply_cbow_batch(self, targets, ctx_ids, ctx_mask, mask, alpha,
                          step):
        lk = self.lookup
        alpha = jnp.float32(alpha)
        mask = jnp.asarray(mask)
        tb = jnp.asarray(targets)
        cb = jnp.asarray(ctx_ids)
        cm = jnp.asarray(ctx_mask)
        if self.use_hs:
            codes, points, pmask = self._path_arrays(targets)
            lk.syn0, lk.syn1, _ = _cbow_hs_step(
                lk.syn0, lk.syn1, cb, cm, codes, points, pmask, mask, alpha,
                dense=_dense_rows(),
            )
        if self.negative > 0:
            negs = jnp.asarray(self._sample_negatives(len(targets), step))
            lk.syn0, lk.syn1neg, _ = _cbow_ns_step(
                lk.syn0, lk.syn1neg, cb, cm, tb, negs, mask, alpha,
                dense=_dense_rows(),
            )

    def _sample_negatives(self, b: int, step: int) -> np.ndarray:
        rng = np.random.RandomState((self.seed + step) % (2**31))
        idx = rng.randint(0, len(self._table), (b, self.negative))
        return self._table[idx]

    # -- query API (reference BasicModelUtils / wordVectors) ----------------

    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        return self.lookup.vector(word)

    def has_word(self, word: str) -> bool:
        return word in self.cache

    def similarity(self, a: str, b: str) -> float:
        """Cosine similarity (reference
        ``BasicModelUtils.similarity``)."""
        ia, ib = self.cache.index_of(a), self.cache.index_of(b)
        if ia < 0 or ib < 0:
            return float("nan")
        m = self.lookup.normalized()
        return float(m[ia] @ m[ib])

    def words_nearest(self, word: str, n: int = 10) -> List[str]:
        """Top-n by cosine (reference ``wordsNearest``) — one matmul
        over the normalized table."""
        i = self.cache.index_of(word)
        if i < 0:
            return []
        m = self.lookup.normalized()
        sims = m @ m[i]
        sims[i] = -np.inf
        top = np.argsort(-sims)[:n]
        return [self.cache.word_at(int(t)) for t in top]

    def words_nearest_vec(self, vec: np.ndarray, n: int = 10) -> List[str]:
        m = self.lookup.normalized()
        v = vec / max(np.linalg.norm(vec), 1e-12)
        sims = m @ v
        top = np.argsort(-sims)[:n]
        return [self.cache.word_at(int(t)) for t in top]


# ---------------------------------------------------------------------------
# Word2Vec
# ---------------------------------------------------------------------------


class Word2Vec(SequenceVectors):
    """Word2Vec over a sentence corpus (reference
    ``models/word2vec/Word2Vec.java`` builder API)."""

    def __init__(self, cache, sentences_ids, **kw):
        super().__init__(cache, **kw)
        self._sentence_ids = sentences_ids

    def _sequences(self):
        return iter(self._sentence_ids)

    class Builder:
        def __init__(self):
            self._min_word_frequency = 1
            self._layer_size = 100
            self._window = 5
            self._lr = 0.5
            self._min_lr = 1e-4
            self._negative = 5
            self._hs = False
            self._sample = 1e-3
            self._epochs = 1
            self._iterations = 1
            self._batch_size = 1024
            self._seed = 12345
            self._algorithm = "SkipGram"
            self._iterator = None
            self._tokenizer = None

        def min_word_frequency(self, n): self._min_word_frequency = n; return self
        def layer_size(self, n): self._layer_size = n; return self
        def window_size(self, n): self._window = n; return self
        def learning_rate(self, x): self._lr = x; return self
        def min_learning_rate(self, x): self._min_lr = x; return self
        def negative_sample(self, n): self._negative = int(n); return self
        def use_hierarchic_softmax(self, b): self._hs = b; return self
        def sampling(self, x): self._sample = x; return self
        def epochs(self, n): self._epochs = n; return self
        def iterations(self, n): self._iterations = n; return self
        def batch_size(self, n): self._batch_size = n; return self
        def seed(self, n): self._seed = n; return self
        def elements_learning_algorithm(self, a): self._algorithm = a; return self
        def iterate(self, it): self._iterator = it; return self
        def tokenizer_factory(self, tf): self._tokenizer = tf; return self

        def build(self) -> "Word2Vec":
            if self._iterator is None:
                raise ValueError("iterate(sentence_iterator) is required")
            tf = self._tokenizer or DefaultTokenizerFactory()
            sentences = [
                tf.create(s).get_tokens() for s in self._iterator
            ]
            cache = VocabConstructor(
                min_word_frequency=self._min_word_frequency
            ).build_vocab_from_tokens(sentences)
            ids = [
                np.asarray(cache.id_stream(toks), np.int64)
                for toks in sentences
            ]
            return Word2Vec(
                cache, ids,
                layer_size=self._layer_size, window=self._window,
                learning_rate=self._lr, min_learning_rate=self._min_lr,
                negative=self._negative, use_hierarchic_softmax=self._hs,
                sample=self._sample, epochs=self._epochs,
                iterations=self._iterations, batch_size=self._batch_size,
                seed=self._seed, algorithm=self._algorithm,
            )
