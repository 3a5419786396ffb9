"""Frames completed ``ok`` in the window over the window's length."""


def read(ctx):
    if ctx.record.window_s <= 0:
        return None
    return len(ctx.record.ok()) / ctx.record.window_s
