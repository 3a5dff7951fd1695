"""Process start to the start of the measured window: loading, the
correctness probe, warm-up and, in a run that compiles, compilation."""


def reduce(run: dict):
    return run["setup_s"]
