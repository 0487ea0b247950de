"""Host seconds of the `setup:weights` spans inside the build
(`setup/weights_s`): the jitted initialiser traced, lowered, compiled or
loaded and dispatched a model, or the checkpoint read."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/weights_s")
