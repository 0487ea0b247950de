"""Backend compilations (or loads from the persistent cache) JAX reported
inside the window: 0 where every step has the same batch (`correct`
fails otherwise), a cost to report where the traffic offers new ones."""


def read(run):
    return run.compiles_in_window
