"""Process start to the first timed request: data, build, warm-up."""


def read(run):
    return run.setup_s
