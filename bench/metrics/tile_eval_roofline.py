"""Share (%) of the tile evaluation's device time that the chip's roofline
would need for the join's work (``bench/work.py``: plain-grid candidates,
points in and result out), against ``bench/peaks.json``."""


def read(ctx):
    t = ctx.value("tile_eval_device_s")
    if not t:
        return None
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    cand = ctx.work.plain_grid_candidates(ctx.x, tr["eps"], cfg["index_dims"])
    w = ctx.work.tile_eval_work(
        ctx.x.shape[0], ctx.x.shape[1], cand, int(ctx.ref.counts.sum()), tr["mode"]
    )
    return 100.0 * ctx.work.least_time(w, ctx.peak()) / t
