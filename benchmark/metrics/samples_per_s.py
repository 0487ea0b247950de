"""Sequences generated, graded, trained and handed back per second of a
whole step, per chip: sequences of one step over the MEDIAN wall seconds of
the timed steps (benchmark clock).  Every step does the same work, so the
median step is the steady step; a step that stalls (about one in ten in
the serving cell gained 1-2.5 s with no compilation, PR 22) shows in
`stall_share`, not here."""
import statistics


def read(run):
    n = statistics.median(len(s["seq_lens"]) for s in run.steps)
    return n / statistics.median(s["wall_s"] for s in run.steps) / run.chips
