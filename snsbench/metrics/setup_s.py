"""Seconds from process start to the first timed operation."""


def read(ctx):
    return ctx.setup_s
