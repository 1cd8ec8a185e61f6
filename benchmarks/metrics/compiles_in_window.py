"""Programs compiled or loaded from the compile cache between the
window's start and its end (``cache_stats()`` hits + misses). Should
read 0: every shape is warmed during set-up."""


def read(ctx):
    return ctx["window"]["compiles"]
