"""Share of the traced window's ``fit`` span spent inside ``next()``
of the iterator it was handed (``fit.feed_wait`` spans of the program;
the pacer's hold is inside them)."""

from benchmarks.harness import fit_spans


def read(ctx):
    tree = fit_spans.of_window()
    return None if tree is None else tree.share("fit.feed_wait")
