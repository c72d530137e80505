"""Fragments: requests to compile or load a program inside the window
(JAX's `backend_compile_duration` events). Warm-up should leave none."""


def read(ctx):
    return ctx["window_compile"]["requests"]
