"""Optimizer steps per program run in the traced window's ``fit()``:
the ``steps`` of its ``fit.dispatch`` spans over their number
(``harness/fit_spans.py``). 16 on the scan path; 1 means the per-step
path ran."""

from benchmarks.harness import fit_spans


def read(ctx):
    tree = fit_spans.of_window()
    return None if tree is None else tree.steps_per_dispatch()
