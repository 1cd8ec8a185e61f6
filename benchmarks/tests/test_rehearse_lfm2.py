"""``lfm2moe.fit_4k`` end to end at its ``tiny`` sizes, on the CPU:
what ``test_rehearse_ling.py`` asks of the delta-rule cell."""

import json

from benchmarks.harness.spec import Cell
from benchmarks.tests.helpers import rehearse

CELL = "lfm2moe.fit_4k"
# accepted metrics that list the cell, and the two it brought; not
# flash_attention_bwd_ms and moe_rows_covered_share, whose lists accepted
# tests pin, and not depthwise_conv_ms, which has nothing to read here
LISTED = {"feed_wait_share", "step_device_ms", "device_idle_share",
          "device_peak_hbm_gib", "compiles_in_window",
          "fit_steps_per_dispatch", "fit_feed_wait_share",
          "fit_stack_share", "fit_dispatch_call_share",
          "fit_host_other_share", "fit_first_dispatch_ms",
          "kernels_named_share", "flash_attention_fwd_ms", "lm_step_mfu",
          "lm_kernels_roofline", "moe_held_slot_share",
          "moe_expert_load_max_over_mean", "setup_trace_s",
          "setup_lower_s", "setup_compile_or_load_s", "setup_cache_misses",
          "short_conv_ms", "short_conv_roofline"}


def _line(earlier, tag):
    return json.loads(next(l for l in earlier if l.startswith(f"[{tag}]"))
                      [len(tag) + 3:])


def test_untraced_run_is_correct_on_the_scan_path(capsys):
    rc, result, earlier, err = rehearse(capsys, CELL, trace=0,
                                        seed=2 ** 31 + 4343)
    assert rc == 0
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] % 16 == 0
    assert set(result["metrics"]) == {"fit_examples_per_s", "setup_s"}
    setup, window, routing = (_line(earlier, tag)
                              for tag in ("setup", "window", "routing"))
    assert setup["scan_program_built"] and not setup["per_step_program_built"]
    cell = Cell(CELL)
    assert setup["params_m"] == round(cell.counts().parameters(
        {**cell.config, **cell.config["tiny"]}) / 1e6, 2)
    # dispatch is off on the CPU: the seven short convolutions take XLA,
    # counted
    assert setup["pallas_dispatch_total"]["short_conv_fwd/xla"] == 7
    assert window["batches"] == window["steps"] == 16 * window["chunks"]
    assert window["compiles"] == 0
    assert routing["moe_dropped_tokens_total"] == 0


def test_traced_run_reports_every_metric_that_lists_the_cell(capsys):
    rc, result, _, _ = rehearse(capsys, CELL, trace=1)
    assert rc == 0 and result["correct"] is True
    assert {m["name"] for m in Cell(CELL).per_layer} == LISTED
    # no TPU plane on the CPU: the device metrics' readers find nothing
    # and the line leaves them out; counters, spans and the host clock
    # are read
    assert set(result["metrics"]) >= {
        "feed_wait_share", "compiles_in_window", "fit_steps_per_dispatch",
        "moe_held_slot_share", "moe_expert_load_max_over_mean"}
    assert not {"short_conv_ms", "short_conv_roofline"} & set(
        result["metrics"])
