"""Process start to the first timed operation: loading, filling the store,
warming up and compiling."""


def read(run):
    return run.setup_s
