"""Rows the train step's grouped MoE dispatch gathered over the (row,
choice) pairs its routers made, mean over layers and micro-batches (the
trainer's step stat `moe/rows_gathered_share`, in %), median step.  One
expert-parallel rank's share gathers a slab of twice a balanced router's
rows — 25% at an eighth of the router's width — and 100% in a layer
whose overflow ran; a program that gathers every pair, as the parent of
PR 41 does, keeps no such stat and the line leaves the metric out."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "stats", lambda st: st["actor_train/moe/rows_gathered_share"]
    )
