"""Process start to the first offered request: weights, deploy, warm-up."""


def read(run):
    return run.setup_s
