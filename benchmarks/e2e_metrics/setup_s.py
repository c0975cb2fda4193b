"""Process start to the first measured request."""


def read(ctx):
    return ctx["setup_s"]
