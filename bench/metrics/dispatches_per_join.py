"""Chunk-program launches per join (``SelfJoinStats.num_device_dispatches``)."""


def read(ctx):
    st = ctx.stats()
    return sum(s.num_device_dispatches for s in st) / len(st) if st else None
