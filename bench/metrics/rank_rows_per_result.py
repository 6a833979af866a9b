"""Rank-select rows per result pair (``SelfJoinStats.rank_rows`` over
``num_results``, summed over the window's joins): the rank windows the
pairs programs were dispatched with, against the pairs they held."""


def read(ctx):
    st = ctx.stats()
    rows = [getattr(s, "rank_rows", 0) for s in st]
    results = sum(s.num_results for s in st)
    if not all(rows) or not results:
        return None
    return sum(rows) / results
