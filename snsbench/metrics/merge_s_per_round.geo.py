"""Median host seconds of the driver's ``merge`` span in the window:
what the master waits for a round's merged top-K."""


def read(ctx):
    return ctx.window.get("merge_s_median")
