"""Points folded by the update() calls of the window, over its wall time."""


def read(ctx):
    if "points" not in ctx.window:
        return None
    return ctx.window["points"] / ctx.window["wall_s"]
