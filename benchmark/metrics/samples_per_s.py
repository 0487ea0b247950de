"""Sequences generated, graded, trained and handed back per second of a
whole step, per chip: sequences of one step over the MEDIAN wall seconds of
the timed steps (benchmark clock).  In a dense cell every step does the
same work, so the median step is the steady step; a step that stalls (about
one in ten in the serving cell gained 1-2.5 s with no compilation, PR 22)
shows in `stall_share`, not here.  In a cell whose step follows its weights
and its tokens (`olmoe-decode-tail`: each step 0.8-0.9% shorter than the one
before as the updates move the router) the steps are NOT the same work;
there the cell's file fixes how many are timed and the draw of its rows
(`timed_steps`, `traffic_seed`) and its configuration's the weights
(`weights_seed`), so the median is the same point of the same trajectory
in every run (`benchmark/run.py`, PERF.md section 6, PR 30)."""
import statistics


def read(run):
    n = statistics.median(len(s["seq_lens"]) for s in run.steps)
    return n / statistics.median(s["wall_s"] for s in run.steps) / run.chips
