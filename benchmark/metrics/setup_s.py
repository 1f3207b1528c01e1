"""Wall of the timed launch less the measured window: process start,
native build, table load, warm-up of every shape (compilation on a cold
cache), the traffic's warm-up, drain and shutdown."""


def read(ctx):
    return ctx["setup_s"]
