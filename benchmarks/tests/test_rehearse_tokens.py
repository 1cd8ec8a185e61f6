"""The token cell end to end at its ``tiny`` sizes, on the CPU: what
``test_rehearse.py`` asks of the two ``fit`` cells, and the readers of
the metrics the cell brings."""

import json

from benchmarks.harness.spec import Cell, load_module
from benchmarks.tests.helpers import rehearse

CELL = "glm47flash.fit_4k"
NEW = {"lm_step_mfu", "lm_kernels_roofline", "moe_held_slot_share",
       "moe_expert_load_max_over_mean"}
# accepted metrics that list the cell: the cell runs fit()'s scan
# driver, its spans, the trace and the flash pair
# (``flash_attention_bwd_ms`` would read here too, but
# ``test_flash_attention_bwd_ms.py`` pins its list to the transformer
# cell alone, and no file the benchmark has may be edited here)
SHARED = {"feed_wait_share", "step_device_ms", "device_idle_share",
          "device_peak_hbm_gib", "compiles_in_window",
          "fit_steps_per_dispatch", "fit_feed_wait_share",
          "fit_stack_share", "fit_dispatch_call_share",
          "fit_host_other_share", "fit_first_dispatch_ms",
          "kernels_named_share", "flash_attention_fwd_ms"}


def test_untraced_run_is_correct_on_the_scan_path(capsys):
    rc, result, earlier, err = rehearse(capsys, CELL, trace=0,
                                        seed=2 ** 31 + 4321)
    assert rc == 0
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] % 16 == 0
    assert set(result["metrics"]) == {"fit_examples_per_s", "setup_s"}
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and "limit" in line
               for line in tail)
    setup, window, routing = (
        json.loads(next(l for l in earlier if l.startswith(f"[{tag}]"))
                   [len(tag) + 3:]) for tag in ("setup", "window", "routing"))
    assert setup["scan_program_built"] and not setup["per_step_program_built"]
    assert setup["layer_runs"] == []
    assert window["batches"] == window["steps"] == 16 * window["chunks"]
    assert window["compiles"] == 0
    assert routing["moe_dropped_tokens_total"] == 0


def test_traced_run_reports_the_cells_metrics_without_error(capsys):
    rc, result, _, _ = rehearse(capsys, CELL, trace=1)
    assert rc == 0 and result["correct"] is True
    names = {m["name"] for m in Cell(CELL).per_layer}
    assert names == NEW | SHARED
    # on the CPU there is no TPU plane: the readers of device metrics
    # find nothing and the line leaves them out; the counters, the
    # spans and the host clock are read
    assert set(result["metrics"]) == {
        "moe_held_slot_share", "moe_expert_load_max_over_mean",
        "feed_wait_share", "compiles_in_window", "fit_steps_per_dispatch",
        "fit_feed_wait_share", "fit_stack_share",
        "fit_dispatch_call_share", "fit_host_other_share",
        "fit_first_dispatch_ms"}
    assert result["metrics"]["fit_steps_per_dispatch"]["value"] == 16
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    share = result["metrics"]["moe_held_slot_share"]["value"]
    assert 10 < share < 45          # 2 of 8 experts held: 25 when even
    assert result["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1


def test_readers_return_nothing_where_the_program_has_no_counters():
    """A program without the routing counters (the parent of the PR
    that brought them): every reader of the cell returns nothing and
    does not raise."""
    class Cpu:
        platform = "cpu"

    ctx = {"window": {"steps": 16, "examples": 32, "seconds": 1.0,
                      "batch": 2},
           "trace": {"planes": 0, "busy_s": 0.0, "by_category_s": {},
                     "by_stem_s": {}},
           "device": Cpu(), "cfg": {}, "counts": None}
    for name in NEW:
        assert load_module("metrics", name).read(ctx) is None


def test_counts_match_the_issues_arithmetic():
    """478M multiply-adds a token forward under even routing, 21.0M of
    an expert layer's 57.0M in causal attention."""
    cell = Cell(CELL)
    counts, cfg = cell.counts(), cell.config
    t = cfg["input"]["length"]
    macs = counts.forward_macs_per_example(cfg) / t
    assert abs(macs - 478.3e6) < 0.2e6
    assert abs(counts.attention_macs_per_example(cfg) / t - 20.97e6) < 1e4
    assert counts.flops_per_example(cfg, 0.25) > counts.flops_per_example(cfg)
