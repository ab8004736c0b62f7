"""setup_s: seconds from the start of the process to the start of the
window: data, build, warm-up (and compiles, in a run that compiles)."""


def read(run):
    return run.setup_s
