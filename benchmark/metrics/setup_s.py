"""Process start to the start of the first timed step: imports, build,
weights, the warm-up step (compilation or cache load), and the reference
check that rides the warm-up step's rollouts."""


def read(run):
    return run.setup_s
