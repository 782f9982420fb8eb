"""setup_s: process start to the first timed round, compile included."""


def read(ctx):
    return ctx["setup_s"]
