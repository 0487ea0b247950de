"""Generate seconds over decode steps run, in ms: the static program runs
`max_new_tokens` steps; the serving plane runs lanes_dispatched /
lane_budget inner steps.  Prefill is inside the numerator."""
import statistics

from benchmark.metrics._labels import GEN


def steps_run(step):
    g = step["gen"]
    if g["lanes_dispatched"]:
        return g["lanes_dispatched"] / g["serving_lane_budget"]
    n_prompts = len(step["seq_lens"])
    return (sum(step["seq_lens"]) - sum(step["prompt_lens"])) / n_prompts


def read(run):
    return statistics.median(
        1e3 * s["spans"][GEN] / steps_run(s) for s in run.steps
    )
