"""Device cache, first touch: host clock around each operation's first run,
less the seconds JAX spent compiling or loading programs in it — encode and
upload of the columns the mix reads."""


def read(ctx):
    return sum(f["wall_s"] - f["compile_s"] for f in ctx["first_touch"])
