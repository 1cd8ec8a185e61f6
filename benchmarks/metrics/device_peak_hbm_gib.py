"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, in GiB: the allocator's peak of live arrays. What the loaded
programs reserve for their temporaries is not in it (the ``[window]``
line gives ``memory_reserved_peak_bytes`` beside it)."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
