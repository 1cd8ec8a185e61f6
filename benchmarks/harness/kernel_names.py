"""The program's Mosaic kernels in a reduced device trace, by name.

Every ``pl.pallas_call`` of the program's kernel library
(``deeplearning4j_tpu/ops/*.py``) passes ``name="<kernel>_<pass>_<dtype
and shapes>"``, and XLA names the custom call after it, inside the
transform scopes it ran under: ``%conv_block_fwd_bfloat16_...`` in a
forward program, ``%transpose_jvp_conv_block_bwd_data_float32_...__``
in a backward pass. So a name is looked for anywhere in an operation's
stem (``trace_reduce.stem_of``), among the operations of category
``tpu_custom_call``. A program from before the names has stems such as
``transpose_jvp__``, which hold none of them.
"""

KERNELS = ("conv_block_", "matmul_block_", "flash_attention_",
           "lstm_cell_", "lstm_sequence_")
CATEGORY = " [tpu_custom_call]"


def seconds(trace, names):
    """(device self time of the ``tpu_custom_call`` stems that hold one
    of ``names``, that of all ``tpu_custom_call`` stems)."""
    stems = {k[:-len(CATEGORY)]: v for k, v in trace["by_stem_s"].items()
             if k.endswith(CATEGORY)}
    return (sum(v for k, v in stems.items()
                if any(n in k for n in names)),
            sum(stems.values()))


def ms_per_step(ctx, name):
    """Device self time per optimizer step of the kernels whose name
    holds ``name``; nothing where the trace has none."""
    spent, _ = seconds(ctx["trace"], (name,))
    steps = ctx["window"]["steps"]
    return 1e3 * spent / steps if spent and steps else None
