"""From the start of the traced window's ``fit`` span to the start of
its first ``fit.dispatch``: the ramp during which the device has
nothing of this window to run."""

from benchmarks.harness import fit_spans


def read(ctx):
    tree = fit_spans.of_window()
    return None if tree is None else 1e3 * tree.first_dispatch_s()
