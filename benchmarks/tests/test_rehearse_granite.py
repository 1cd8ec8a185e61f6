"""``granite40hmicro.fit_4k`` end to end at its ``tiny`` sizes, on the
CPU: what ``test_rehearse_tokens.py`` asks of the other token cell."""

import json

from benchmarks.harness.spec import Cell
from benchmarks.tests.helpers import rehearse

CELL = "granite40hmicro.fit_4k"
# accepted metrics that listed the cell when it was added (it brought
# no reader of its own); a later metric lists it in a file of its own
LISTED = {"feed_wait_share", "step_mfu", "step_device_ms",
          "kernels_roofline", "device_idle_share", "device_peak_hbm_gib",
          "compiles_in_window", "fit_steps_per_dispatch",
          "fit_feed_wait_share", "fit_stack_share",
          "fit_dispatch_call_share", "fit_host_other_share",
          "fit_first_dispatch_ms", "kernels_named_share",
          "flash_attention_fwd_ms"}


def _line(earlier, tag):
    return json.loads(next(l for l in earlier if l.startswith(f"[{tag}]"))
                      [len(tag) + 3:])


def test_untraced_run_is_correct_on_the_scan_path(capsys):
    rc, result, earlier, err = rehearse(capsys, CELL, trace=0,
                                        seed=2 ** 31 + 3737)
    assert rc == 0
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] % 16 == 0
    assert set(result["metrics"]) == {"fit_examples_per_s", "setup_s"}
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and "limit" in line
               for line in tail)
    setup, window, routing = (_line(earlier, tag)
                              for tag in ("setup", "window", "routing"))
    assert setup["scan_program_built"] and not setup["per_step_program_built"]
    # every trained number of the tiny stack, the tied table once
    assert setup["params_m"] == round(
        Cell(CELL).counts().parameters(
            {**Cell(CELL).config, **Cell(CELL).config["tiny"]}) / 1e6, 2)
    assert window["batches"] == window["steps"] == 16 * window["chunks"]
    assert window["compiles"] == 0
    # no expert layer: nothing dropped (the registry is the process's,
    # so the other token cell's counts may stand in it)
    assert routing["moe_dropped_tokens_total"] == 0


def test_traced_run_reports_every_metric_that_lists_the_cell(capsys):
    rc, result, _, _ = rehearse(capsys, CELL, trace=1)
    assert rc == 0 and result["correct"] is True
    assert {m["name"] for m in Cell(CELL).per_layer} >= LISTED
    # on the CPU there is no TPU plane: the readers of device metrics
    # find nothing and the line leaves them out; the counters, the
    # spans and the host clock are read
    assert set(result["metrics"]) >= {
        "feed_wait_share", "compiles_in_window", "fit_steps_per_dispatch",
        "fit_feed_wait_share", "fit_stack_share",
        "fit_dispatch_call_share", "fit_host_other_share",
        "fit_first_dispatch_ms"}
    assert result["metrics"]["fit_steps_per_dispatch"]["value"] == 16
    assert result["metrics"]["compiles_in_window"]["value"] == 0
