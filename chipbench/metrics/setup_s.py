"""Process start to the first timed operation, compiles included."""


def read(run):
    return run.setup_s
