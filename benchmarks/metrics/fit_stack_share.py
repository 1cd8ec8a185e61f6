"""Share of the traced window's ``fit`` span spent stacking a chunk's
host batches and enqueueing their copy to the device (``fit.stack``
spans of the program)."""

from benchmarks.harness import fit_spans


def read(ctx):
    tree = fit_spans.of_window()
    return None if tree is None else tree.share("fit.stack")
