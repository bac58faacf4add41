"""From the start of the process to the start of the first measured call:
weights, building the system, and warming (or compiling) every shape."""


def read(run):
    return run.setup_s
