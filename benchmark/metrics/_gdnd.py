"""What the readers of Gated DeltaNet mixers before a DENSE MLP share
(olmo_hybrid on the static decode program): whether the run's model is
one.  Every reader returns None for another model and for a program that
keeps no such scope or counter."""


def is_gdnd(run):
    cfg = run.model_cfg
    return bool(getattr(cfg, "is_hybrid", False)) and not cfg.is_moe
