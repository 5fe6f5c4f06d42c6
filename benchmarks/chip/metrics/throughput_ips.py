"""Inferences whose outputs reached the host inside the window, per second
from the window's start to the last of them (MLPerf Offline's duration)."""


def read(ctx):
    return ctx["run"]["completed"] / (ctx["run"]["last_done"] - ctx["run"]["window"][0])
