"""setup_s: seconds from process start to the start of the measured window:
JAX's start-up, data drawn on the device, ``Index.build``, warm-up and, in a
run that compiles, compilation (host clock)."""


def read(ctx):
    return ctx.setup_s
