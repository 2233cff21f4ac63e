"""Set-up seconds: process start to the window's opening (imports, weights
from the seed, the fleet, warm-up with its compiles or cache loads)."""


def read(run):
    return run.setup_s
