"""1 - busy / window on the TPU plane of the traced window."""


def read(ctx):
    trace = ctx["trace"]
    if not trace["planes"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
