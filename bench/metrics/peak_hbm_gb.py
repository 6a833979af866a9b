"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``, GB)
on the fullest chip, read after the window."""


def read(ctx):
    b = ctx.memory_peak_bytes
    return b / 1e9 if b else None
