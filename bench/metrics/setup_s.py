"""Seconds from process start to the first timed send: loading, building
the tenant pool, starting the service, and compiling (or loading from
the compile cache) every shape the window uses."""


def read(run):
    return run.setup_s
