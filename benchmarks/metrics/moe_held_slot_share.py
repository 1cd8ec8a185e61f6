"""Token-slots the router sent to experts held on this chip, as a share
of all it sent (tokens x experts per token), every expert layer
together, since the weights were made: ``moe_token_slots_total{layer,
held}`` of the program's metrics registry. 8 of 64 experts under even
routing give 12.5; it is the chip's load against the group's mean."""


def read(ctx):
    slots = (ctx.get("routing") or {}).get("moe_token_slots_total")
    if not slots:
        return None
    held = sum(v for k, v in slots.items() if k.endswith("/true"))
    total = sum(slots.values())
    return 100.0 * held / total if total else None
