"""setup_s: seconds from the process's start to the first timed request:
JAX's start, the tenants' set-up and the warm-up of every shape."""


def read(ctx):
    return ctx.setup_s
