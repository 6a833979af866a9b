"""Point pairs the index keeps as candidates per result pair
(``SelfJoinStats.num_candidates / num_results``): the index's filtering."""


def read(ctx):
    st = ctx.stats()
    if not st or not st[-1].num_results:
        return None
    return st[-1].num_candidates / st[-1].num_results
