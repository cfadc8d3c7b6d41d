"""A time the harness took on its own clock around a phase of the run."""


def read(ctx, key):
    return ctx.get(key)
