"""setup_s: process start to the window's open (weights, deployment,
compilation or its cache load, the mix's warm phase)."""


def read(ctx):
    return ctx.setup_s
