"""Real tokens over grid tokens of the train engine's packed rows (its
`last_pack_stats`, the step's last minibatch), in %."""
import statistics


def read(run):
    vals = [s["pack"]["pack_efficiency"] for s in run.steps if s["pack"]]
    return 100.0 * statistics.median(vals) if vals else None
