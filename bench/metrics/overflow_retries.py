"""Pairs-buffer or rank-window retries per join (``SelfJoinStats.overflow_retries``)."""


def read(ctx):
    st = ctx.stats()
    return sum(s.overflow_retries for s in st) / len(st) if st else None
