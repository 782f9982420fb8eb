"""setup.compile_s: seconds of backend compilation JAX reported before the
window (its compile-duration events); near 0 when the persistent cache
holds every program."""


def read(ctx):
    return ctx["compile_s"]
