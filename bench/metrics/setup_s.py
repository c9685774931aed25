"""Process start to the first request of the window, compiling included."""


def read(run):
    return run.setup_s
