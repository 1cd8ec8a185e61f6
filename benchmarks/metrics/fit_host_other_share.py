"""Share of the traced window's ``fit`` span outside feed wait,
stacking and dispatch: the self time of ``fit`` and ``fit.epoch``
(preemption and control-plane checks, signatures, bookkeeping) plus
the listeners. With the three other ``fit_*_share`` it sums to 100."""

from benchmarks.harness import fit_spans


def read(ctx):
    tree = fit_spans.of_window()
    return None if tree is None else 100.0 * tree.other() / tree.seconds
