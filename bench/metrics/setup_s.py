"""Process start to the first timed request or step, compiles included."""


def value(run):
    return run.out["setup_s"]
