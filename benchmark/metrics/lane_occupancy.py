"""Live lanes over dispatched lanes of the serving chunk, in %."""


def read(run):
    disp = run.total(lambda s: s["gen"]["lanes_dispatched"])
    if not disp:
        return None  # not the serving plane
    return 100.0 * run.total(lambda s: s["gen"]["lanes_live"]) / disp
