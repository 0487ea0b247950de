"""Bytes of the slots' recurrent state and conv tails over those plus the
most bytes of pages mapped at once (the generator's counters
`ssm_state_bytes` + `ssm_conv_bytes` and `peak_allocated_bytes`), in %,
median step: what a request holds beside its pages on the serving plane.
The state does not grow with the answer; pages do."""
from benchmark.metrics._program import step_median


def read(run):
    def share(p):
        slots = p["ssm_state_bytes"] + p["ssm_conv_bytes"]
        return 100.0 * slots / (slots + p["peak_allocated_bytes"])

    return step_median(run, "pool", share)
