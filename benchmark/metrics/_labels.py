"""Span labels `benchmark/run.py` gives the worker requests of a PPO
step (request type, then the model or hook target it names)."""

GEN = "actor_gen:generate"
TRAIN = "actor:train_step"
REWARD = "reward:inference"
HANDBACK = "param_sync:actor_gen"  # the colocated re-layout


def handback(step):
    return step["spans"].get(HANDBACK, 0.0)
