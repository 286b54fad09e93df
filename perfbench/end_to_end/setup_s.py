"""Process start to the first instant of the window: child start, cluster
load, warm-up and, in a run that compiles, compilation."""


def compute(run: dict):
    return run["setup_s"]
