"""Rows the held experts' data path covered (the static row bound of
the rung each call ran at, summed over the calls) as a share of the
token-slots routed (tokens x experts per token), every expert layer
together, since the weights were made: ``rows_covered`` beside
``slots`` in what ``publish_routing_metrics`` returns. 25 when every
call of a layer that holds 8 of 64 experts ran at the first rung, 100
when the path covers every slot; nothing on a program that counts no
rungs."""


def read(ctx):
    layers = [t for t in ((ctx.get("routing") or {}).get("layers")
                          or {}).values() if "rows_covered" in t]
    slots = sum(sum(t["slots"]) for t in layers)
    if not slots:
        return None
    return 100.0 * sum(t["rows_covered"] for t in layers) / slots
