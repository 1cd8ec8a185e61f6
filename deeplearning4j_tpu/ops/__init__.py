"""Custom Pallas TPU kernels for the fusion-critical ops (SURVEY.md
§2.3 maps libnd4j's hand-written kernels here). Everything else stays
plain jax.numpy/lax — XLA's fusion already covers it; notably the
embedding scatter-add and negative-sampling updates lower to native
TPU scatter ops via ``jnp.ndarray.at``/``segment_sum``, so a custom
kernel would only re-derive what the compiler emits.

Block-size selection is centralized: ``ops/tiling.py`` owns the VMEM
budget and every divisor heuristic, and ``ops/autotune.py`` runs the
measured tiling search over the same candidate space
(``DL4J_TPU_TUNE`` = off / cached / on) with winners persisted under
``DL4J_TPU_TUNE_CACHE_DIR``."""

from deeplearning4j_tpu.ops.autotune import (
    tuning_active,
    tuning_mode,
)
from deeplearning4j_tpu.ops.flash_attention import flash_attention, mha
from deeplearning4j_tpu.ops.lstm_cell import (
    lstm_cell,
    lstm_cell_diff,
    use_pallas_lstm,
)
from deeplearning4j_tpu.ops.matmul_block import (
    SUPPORTED_EPILOGUES,
    matmul_block,
    matmul_block_ok,
    matmul_block_reference,
)
from deeplearning4j_tpu.ops.tiling import VMEM_BUDGET_BYTES

__all__ = ["flash_attention", "mha", "lstm_cell", "lstm_cell_diff",
           "use_pallas_lstm", "matmul_block", "matmul_block_ok",
           "matmul_block_reference", "SUPPORTED_EPILOGUES",
           "tuning_active", "tuning_mode", "VMEM_BUDGET_BYTES"]
