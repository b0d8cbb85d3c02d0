"""Peak device memory of the fullest chip after the window and before the
reference comparison, in GB (1e9 bytes): ``live_peak_bytes`` is
``memory_stats()["peak_bytes_in_use"]`` (weights, state, the KV pool),
``temp_peak_bytes`` is ``peak_bytes_reserved`` (the temporaries the largest
program reserved while it ran)."""


def read(ctx, key):
    peak = (ctx.get("memory") or {}).get(key)
    return peak / 1e9 if peak else None
