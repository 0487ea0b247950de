"""The warm-up step's wall (`time/step_s`) less the seconds its MFC
replies spent tracing, lowering and in the backend phase, loads included
(`<node>/perf/trace_s`, `/perf/lower_s`, `/perf/compile_s`): execution,
the reference check's own arithmetic, the hand-back, and programs
compiled outside any MFC."""
from benchmark.metrics import _setup


def read(run):
    programs = _setup.total(
        run, "perf/trace_s", "perf/lower_s", "perf/compile_s"
    )
    if programs is None:
        return None
    return _setup.stats(run)["time/step_s"] - programs
