"""Each cell end to end at its ``tiny`` sizes, on the CPU."""

import json

import pytest

from benchmarks.harness.spec import Cell
from benchmarks.tests.helpers import CELLS, rehearse


@pytest.mark.parametrize("workload", CELLS)
def test_untraced_run_reports_the_end_to_end_metrics(capsys, workload):
    rc, result, earlier, err = rehearse(capsys, workload, trace=0,
                                        seed=2 ** 31 + 12345)
    assert rc == 0
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] % 16 == 0
    assert set(result["metrics"]) == {"fit_examples_per_s", "setup_s"}
    assert result["metrics"]["fit_examples_per_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and "limit" in line
               for line in tail)
    # the earlier lines say which path ran: the scan program, whole
    # chunks, nothing compiled in the window
    setup = json.loads(next(l for l in earlier
                            if l.startswith("[setup]"))[len("[setup] "):])
    window = json.loads(next(l for l in earlier
                             if l.startswith("[window]"))[len("[window] "):])
    assert setup["scan_program_built"] and not setup["per_step_program_built"]
    assert window["batches"] == window["steps"] == 16 * window["chunks"]
    assert window["compiles"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_the_per_layer_metrics(capsys, workload):
    rc, result, _, _ = rehearse(capsys, workload, trace=1)
    assert rc == 0 and result["correct"] is True
    names = {m["name"] for m in Cell(workload).per_layer}
    # on the CPU there is no TPU plane: the readers of device metrics
    # find nothing and the harness leaves them out, never a 0
    assert set(result["metrics"]) <= names
    assert {"feed_wait_share", "compiles_in_window"} <= set(result["metrics"])
    assert "step_mfu" not in result["metrics"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])


def test_no_chip_and_no_rehearse_is_an_error(capsys):
    from benchmarks import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "resnet50.fit", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert not capsys.readouterr().out.strip().startswith("{")
