"""XLA compile: seconds JAX spent compiling programs, or loading them from
the persistent cache, during set-up."""


def read(ctx):
    return ctx["setup_compile"]["seconds"]
