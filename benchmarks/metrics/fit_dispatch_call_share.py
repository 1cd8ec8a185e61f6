"""Share of the traced window's ``fit`` span spent inside the call
that enqueues a program run (``fit.dispatch`` spans of the program): a
compile, a trace-cache miss or a full launch queue shows here."""

from benchmarks.harness import fit_spans


def read(ctx):
    tree = fit_spans.of_window()
    return None if tree is None else tree.share("fit.dispatch")
