"""Device busy seconds per million points folded in the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.window.get("points"):
        return None
    return ctx.trace["busy_s"] / (ctx.window["points"] * 1e-6)
