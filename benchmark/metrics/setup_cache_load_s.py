"""Seconds of the backend phase of every program the persistent cache
SERVED, process start to the end of the warm-up step
(`setup/cache_load_s`): the key's hash, the read, the executable's
deserialisation."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/cache_load_s")
