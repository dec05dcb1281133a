"""Seconds from the start of the process until the window opens: JAX
start-up, weights, engine, compiles (or cache loads) and warm-up traffic."""


def read(ctx):
    return ctx.setup_s
