"""Device busy time per optimizer step in the traced window: the union
of the operations' intervals on the TPU plane over the steps."""


def read(ctx):
    trace, w = ctx["trace"], ctx["window"]
    if not trace["planes"] or not trace["busy_s"] or not w["steps"]:
        return None
    return 1e3 * trace["busy_s"] / w["steps"]
