"""Host-clock seconds from process start to the window's start: JAX start,
keys, data, compile-cache loads (or compiles), init and share phases and
the warm round."""


def read(run):
    return run.setup_s
