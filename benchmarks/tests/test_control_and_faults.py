"""``correct`` has to come out false for the control (the reference in
the next lower precision, in the program's place) and for each fault a
training cell can have, planted underneath the timed path of an
otherwise whole run."""

import pytest

from benchmarks.tests.helpers import CELLS, rehearse


@pytest.mark.parametrize("workload", CELLS)
def test_the_float8_control_is_not_correct(workload):
    from types import SimpleNamespace

    from benchmarks.drivers.fit import FitRun
    from benchmarks.harness import compare
    from benchmarks.harness.spec import Cell

    fr = FitRun(Cell(workload), SimpleNamespace(rehearse=True))
    fr.start(5, warmup_chunks=1)
    reference = fr.reference_readings()
    steps, limits = fr.traffic["loss_steps"], fr.traffic["limits"]
    ok, _ = compare.decide(
        compare.numbers(fr.program, reference, steps)[0], limits)
    assert ok
    control = fr.reference_readings(compute="float8")
    ok, checks = compare.decide(
        compare.numbers(control, reference, steps)[0], limits)
    assert not ok, checks


@pytest.mark.parametrize("workload", CELLS)
def test_the_tiny_size_is_held_to_the_same_numbers(workload):
    """The faults below are planted at the ``tiny`` size: it has to be
    held to the numbers the cell's own size is held to, no more."""
    from benchmarks.harness.spec import Cell

    traffic = Cell(workload).traffic
    assert set(traffic["tiny"]["limits"]) == set(traffic["limits"])


@pytest.mark.parametrize("workload", CELLS)
def test_program_and_reference_take_the_same_rates(workload):
    """The configuration's schedule as the program reads it and as the
    reference does: the first step at the rate, the rest of the first
    chunk at nought, the rate again from the second chunk on."""
    from benchmarks.drivers.fit import build_program, sized
    from benchmarks.harness.reference_train import learning_rates
    from benchmarks.harness.spec import Cell

    cfg = sized(Cell(workload).config, True)
    rate = cfg["updater"]["learning_rate"]
    want = [rate] + [0.0] * 15 + [rate] * 4
    assert learning_rates(cfg["updater"], 20) == want
    net = build_program(cfg, 1)
    for it, lr in enumerate(want):
        assert set(net.updater_def.scheduled_lrs(it).values()) == {lr}


@pytest.mark.parametrize("workload", CELLS)
def test_a_step_that_returns_its_state_unchanged(capsys, monkeypatch,
                                                 workload):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import core

    real = core.build_multi_step

    def broken(*args, **kwargs):
        step = real(*args, **kwargs)

        def unchanged(params, upd_state, state, *rest):
            keep = jax.tree.map(jnp.copy, (params, upd_state, state))
            out = step(params, upd_state, state, *rest)
            return (*keep, *out[3:])

        return unchanged

    monkeypatch.setattr(core, "build_multi_step", broken)
    _, result, _, _ = rehearse(capsys, workload)
    assert result["correct"] is False
    # every leaf's change is missing: the worst leaf's gap is 1
    assert 0.9 <= result["checks"]["delta_gap"]["value"] <= 1.0


@pytest.mark.parametrize("workload", CELLS)
def test_half_of_the_batch_left_out(capsys, monkeypatch, workload):
    from deeplearning4j_tpu.nn import core, multilayer

    real = core.stack_on_device

    def half(arrs, dtype):
        return real([a[: len(a) // 2] for a in arrs], dtype)

    monkeypatch.setattr(core, "stack_on_device", half)
    monkeypatch.setattr(multilayer, "_stack_on_device", half)
    _, result, _, _ = rehearse(capsys, workload)
    assert result["correct"] is False
