"""Device busy seconds summed over the chips per million points folded
in the traced window.  The busy time holds every op of the window: the
sites' folds and the master's merge programs alike, so this reads the
two layers together.  It is the one-chip
``fold_device_s_per_mpoint.ingest`` plus the merge's device seconds per
million points, if sharding the fold costs nothing."""


def read(ctx):
    if ctx.trace is None or not ctx.window.get("points"):
        return None
    return ctx.trace["busy_s"] * ctx.trace["chips"] / (
        ctx.window["points"] * 1e-6)
