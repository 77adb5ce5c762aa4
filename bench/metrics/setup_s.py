"""setup_s (s): process start to the first timed unit: JAX and TPU start-up,
inputs and weights drawn from the seed, compile or compile-cache load,
warm-up. Host clock."""


def read(ctx):
    return ctx.setup_seconds
