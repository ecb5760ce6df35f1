"""Set-up: process start to the window's start. Weights and traffic made
from the seed, storage built, every shape compiled or loaded from the
persistent cache, pre-warm batches served."""
UNIT = "s"


def read(run):
    return run.setup_s
