#!/usr/bin/env bash
# Run the fault-injection (chaos) test subset with a fixed seed.
#
# Every chaos-marked test derives its failure schedules from
# DL4J_TPU_CHAOS_SEED (default 1337), so a red run here reproduces
# bit-for-bit: re-run with the same seed to replay the exact same
# injected faults. Override the seed to explore other schedules:
#
#   DL4J_TPU_CHAOS_SEED=7 scripts/run_chaos.sh
#
# Extra pytest args pass through (e.g. -k retry, -x). Each storm
# suite runs as its own pytest invocation with a faulthandler
# timeout (a hung storm dumps every thread's stack instead of dying
# silently), and the run ends with a per-storm pass/fail summary —
# the exit code is nonzero iff any storm failed.
set -uo pipefail
cd "$(dirname "$0")/.."

export DL4J_TPU_CHAOS_SEED="${DL4J_TPU_CHAOS_SEED:-1337}"
echo "chaos seed: ${DL4J_TPU_CHAOS_SEED}"

# Preamble: the metric signal catalog (docs/ARCHITECTURE.md) must
# match the names registered in code — drift fails loudly here,
# before the chaos suite spends a second (see scripts/lint_metrics.py).
python scripts/lint_metrics.py || exit 1
# ... and both engine wrappers must still delegate their hot paths to
# the unified functional core, nn/core.py (no reintroduced duplicate
# step/scan/remat implementations — see scripts/lint_parity.py).
python scripts/lint_parity.py || exit 1

# Registered chaos storms (suite -> what the storm asserts):
#   tests/test_resilience.py     — training runtime (retry/checkpoint/
#                                  guard, kill/resume incl. prefetch,
#                                  deadline-capped retry storms)
#   tests/test_serving.py        — serving tier (breaker + fault storms)
#   tests/test_batching.py       — micro-batch drain loop (seeded storms
#                                  through the batched path: sequential
#                                  determinism + concurrent chunk faults)
#   tests/test_input_pipeline.py — prefetch pipeline (flaky-source
#                                  storms surface as DL4JFaultException;
#                                  guarded bad-step trajectory
#                                  equivalence under async dispatch;
#                                  bounded shutdown re-raises pending
#                                  worker faults)
#   tests/test_compile.py        — compile artifacts (corrupted /
#                                  stale AOT bundles must degrade
#                                  silently to JIT, never error the
#                                  request path or the restore)
#   tests/test_fleet.py          — serving fleet (SIGKILL one backend
#                                  process under router load: zero
#                                  request loss via retries, backend
#                                  restarts warm from the shared
#                                  persistent compile cache and
#                                  rejoins on the next health poll;
#                                  wedged-backend /readyz probe
#                                  timeouts mark unhealthy instantly)
#   tests/test_loop.py           — continuous-learning loop, four
#                                  storms: kill the trainer mid-epoch
#                                  (bitwise resume, with prefetch +
#                                  artifacts in test_resilience.py),
#                                  corrupt the candidate checkpoint
#                                  (quarantined; live keeps serving),
#                                  fail the canary (rejected; old
#                                  version untouched), SIGKILL
#                                  mid-promotion (journal recovery
#                                  rolls the half-applied promotion
#                                  forward) — plus the traffic-shift
#                                  regression rollback with zero XLA
#                                  compiles, counter-asserted
#   tests/test_preemption.py     — preemption notices: SIGTERM
#                                  mid-epoch with prefetch + async
#                                  dispatch live -> emergency
#                                  checkpoint, exit code 75, bitwise
#                                  resume on both engines; the same
#                                  storm with megastep=K live (SIGTERM
#                                  mid-chunk -> emergency checkpoint on
#                                  the last chunk boundary, staleness
#                                  <= K-1, bitwise megastep resume);
#                                  ModelServer + ServingRouter drain
#                                  with zero 5xx
#   tests/test_elastic.py        — device loss mid-run -> survivor-
#                                  mesh recovery from the host-RAM
#                                  snapshot ring (no steps lost beyond
#                                  the last snapshot); the same storm
#                                  with zero=True ZeRO-sharded
#                                  optimizer state (8->4 survivors
#                                  re-shard the moments, bitwise vs a
#                                  piecewise reference); injected
#                                  straggler -> straggler_detected_total
#   tests/test_data_defense.py   — bad-data storms: seeded
#                                  PoisonIterator feeds K corrupt of N
#                                  batches -> exactly K quarantines by
#                                  reason and final params bitwise the
#                                  clean run over the N-K survivors
#                                  (both engines + distributed trainer
#                                  with prefetch); statistical-guard
#                                  spike trips with checkpointed EWMA
#                                  + skipped-batch ledger (bitwise
#                                  resume); continual trainer dies
#                                  between publishes mid-quarantine
#                                  and resumes bitwise off the
#                                  manifest's data ledger
#   tests/test_control_plane.py  — cross-host control plane: lease
#                                  heartbeats through seeded drop /
#                                  delay / partition storms (drops
#                                  survive the retry envelope, delays
#                                  land in control_rtt_ms, a hard
#                                  partition concludes coordinator
#                                  lost -> emergency checkpoint +
#                                  exit 75); then the real thing —
#                                  two jax.distributed processes,
#                                  rank 1 SIGKILLed mid-step, the
#                                  survivor rolls back to the newest
#                                  snapshot, re-forms a 1-process
#                                  mesh, and finishes bitwise equal
#                                  to a piecewise reference, with
#                                  ZeRO off and on (sharded moments
#                                  gathered + re-sharded)
#   tests/test_async_checkpoint.py — write-behind sharded checkpoints:
#                                  a control-channel partition DURING
#                                  the two-phase commit barrier (both
#                                  hosts abort, agree on the previous
#                                  committed step, torn dir GC'd);
#                                  SIGKILL swept across the async
#                                  write's phases single-process
#                                  (restore lands the newest committed
#                                  step, resume bitwise equal to the
#                                  uninterrupted reference); the real
#                                  2-process sharded storm, ZeRO off
#                                  and on (rank 1 dies right after
#                                  enqueuing its save — the commit
#                                  either lands whole or aborts, and
#                                  the restored shards merge bitwise
#                                  onto a 1-device mesh)
#   tests/test_autotune.py       — kernel tuning cache: a seeded storm
#                                  mangles persisted entries (truncate,
#                                  garbage bytes, flipped fingerprint,
#                                  infeasible config, deleted file)
#                                  between resolves — every mangled
#                                  read must degrade to the divisor
#                                  heuristic (counted by reason in
#                                  tuner_fallback_total), never crash,
#                                  never dispatch a mangled config;
#                                  dispatch outputs stay bitwise equal
#                                  to tuning off throughout
#   tests/test_embeddings.py     — sharded embeddings: a ShardedWord2Vec
#                                  run on the 8-device mesh is killed
#                                  with os._exit(137) at a seed-derived
#                                  step mid-epoch (no cleanup, no
#                                  flush); a second process restores the
#                                  last write-behind checkpoint on ONE
#                                  device and finishes — final tables
#                                  bitwise equal to an uninterrupted
#                                  run (the canonical-host-rows +
#                                  mesh-independent-update contract)
STORMS=(
    tests/test_resilience.py
    tests/test_serving.py
    tests/test_batching.py
    tests/test_input_pipeline.py
    tests/test_compile.py
    tests/test_fleet.py
    tests/test_loop.py
    tests/test_preemption.py
    tests/test_elastic.py
    tests/test_data_defense.py
    tests/test_autotune.py
    tests/test_profiler.py
    tests/test_control_plane.py
    tests/test_async_checkpoint.py
    tests/test_embeddings.py
)

declare -a names rcs
failed=0
for storm in "${STORMS[@]}"; do
    echo
    echo "=== storm: ${storm} ==="
    env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python -m pytest "${storm}" \
        -q -m chaos \
        -o faulthandler_timeout=300 \
        -p no:cacheprovider -p no:xdist -p no:randomly "$@"
    rc=$?
    # pytest rc 5 = "no tests collected" (e.g. -k filtered a suite
    # to nothing): not a storm failure
    if [ "$rc" -eq 5 ]; then rc=0; fi
    names+=("${storm}")
    rcs+=("${rc}")
    if [ "$rc" -ne 0 ]; then failed=1; fi
done

echo
echo "=== chaos storm summary (seed ${DL4J_TPU_CHAOS_SEED}) ==="
for i in "${!names[@]}"; do
    if [ "${rcs[$i]}" -eq 0 ]; then
        echo "  PASS  ${names[$i]}"
    else
        echo "  FAIL  ${names[$i]} (exit ${rcs[$i]})"
    fi
done
exit "${failed}"
