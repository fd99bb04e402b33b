"""Process start to the first timed step: imports, initialisation,
traffic, compilation or cache load, and the warm-up steps."""


def read(run):
    return run.setup_s
