"""Share of the traced window that ``fit()`` spent inside ``next()`` and
``has_next()`` of the outermost iterator it was handed (host clock, by
the harness's ``TimedIterator``)."""


def read(ctx):
    w = ctx["window"]
    return 100.0 * w["feed_wait_s"] / w["seconds"]
