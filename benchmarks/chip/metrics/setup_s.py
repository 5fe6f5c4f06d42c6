"""Set-up: process start to the first measured query (imports, device
start, dispatch, lowering, XLA compile or cache read, warm-up)."""


def read(ctx):
    return ctx["setup"]["setup_s"]
