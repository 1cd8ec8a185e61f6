#!/usr/bin/env bash
# TPU-profile test run — the `-P test-nd4j-cuda-8.0` analog
# (SURVEY.md §4): the suite subset that exercises the Pallas kernels /
# conv / rnn / transformer paths, on the TPU backend (Pallas compiled,
# not interpreted; see tests/conftest.py pallas_interpret()).
#
# This sandbox has no chip: send the script through the chip tool,
#   chiprun --timeout 3000 -- bash tests/run_tpu_profile.sh
# and read the log it leaves under chiprun_out/ (the only directory
# that comes back from that machine). A chip belongs to one process at
# a time: the probe below takes it, checks it and EXITS before pytest
# starts, and pytest then runs in one process (no xdist workers here).
# Usage on that machine:  bash tests/run_tpu_profile.sh [outfile]
set -euo pipefail
cd "$(dirname "$0")/.."
OUT="${1:-chiprun_out/tpu_profile_run.log}"
mkdir -p "$(dirname "$OUT")"
# hard gate OUTSIDE the logged group: on a host with no chip the suite
# would silently run Pallas in interpret mode and write a log that
# looks like a chip run
python - <<'PY'
import jax
d = jax.devices()[0]
print(f"backend={jax.default_backend()} device={d.device_kind}")
assert jax.default_backend() == "tpu", "TPU backend required"
PY
{
  echo "== TPU profile run: $(date -u +%Y-%m-%dT%H:%M:%SZ)"
  python -c "import jax; d=jax.devices()[0]; print(f'backend={jax.default_backend()} device={d.device_kind}')"
  # kernel/conv/rnn/transformer paths PLUS (r4, VERDICT #7) the graph
  # engine, solvers, updaters, serialization, pretrain (VAE/RBM
  # sampling under TPU PRNG), NLP XLA steps, transformer KV-cache
  # streaming, config round-trip, and the DP trainer on a 1-chip
  # degenerate mesh (multi-device cases self-skip via require_devices)
  # r5 (VERDICT #9): plus clustering, graph embeddings, eval,
  # datasets, backend-consistency, the w2v full-model suite, zoo
  # smoke, NLP periphery and cluster-NLP — everything chip-compatible
  # (f64 gradient checks stay CPU; multi-device cases self-skip)
  DL4J_TPU_TEST_PLATFORM=tpu python -m pytest \
    tests/test_pallas_ops.py tests/test_cnn.py tests/test_rnn.py \
    tests/test_mlp.py tests/test_transformer.py \
    tests/test_flops_and_device.py \
    tests/test_graph.py tests/test_solvers.py tests/test_updaters.py \
    tests/test_serialization.py tests/test_pretrain.py \
    tests/test_nlp.py tests/test_transformer_streaming.py \
    tests/test_config.py tests/test_parallel.py \
    tests/test_clustering.py tests/test_graph_embeddings.py \
    tests/test_eval_meta.py tests/test_datasets.py \
    tests/test_backend_consistency.py tests/test_w2v_full_model.py \
    tests/test_zoo.py tests/test_nlp_periphery.py \
    tests/test_cluster_nlp.py \
    -q --no-header
} 2>&1 | tee "$OUT"
