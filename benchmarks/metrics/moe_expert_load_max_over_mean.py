"""The busiest expert's token-slots over the mean of its layer's
experts (all the router scores, held or not), since the weights were
made; the worst expert layer's: the gauge
``moe_expert_load_max_over_mean{layer}`` of the program's metrics
registry. 1 is even routing."""


def read(ctx):
    gauge = (ctx.get("routing") or {}).get("moe_expert_load_max_over_mean")
    return max(gauge.values()) if gauge else None
