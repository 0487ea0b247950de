"""What the readers of dots3_note share (latent attention behind a token
indexer beside latent window layers, on the static decode program):
whether the run's model is one, and device milliseconds a decode iteration
under a scope.  Every reader returns None for another model and for a
program that keeps no such scope or counter."""
from benchmark.metrics import decode_ms_per_step
from benchmark.metrics._program import scope_seconds


def is_dsa(run):
    cfg = run.model_cfg
    return bool(
        getattr(cfg, "kv_lora_rank", 0) and getattr(cfg, "window_pattern", "")
        and getattr(cfg, "swa_n_heads", 0))


def decode_ms(run, *needles):
    """Device milliseconds a decode iteration under `gen/decode_step` (and
    `needles`), all such layers of one iteration together, traced run;
    None without the scope."""
    if not is_dsa(run):
        return None
    seconds = scope_seconds(run, "gen/decode_step", *needles)
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])


def train_share(run, *needles):
    """Device seconds of the gradient program under `needles` (forward,
    recomputed forward and backward) over all of `train/grad`'s, in %."""
    if not is_dsa(run):
        return None
    part = scope_seconds(run, "train/grad", *needles)
    whole = scope_seconds(run, "train/grad")
    if part is None or whole is None:
        return None
    return 100.0 * part / whole


def contexts(run):
    """Each row's mean cache length over the last step's decode loop."""
    step = run.steps[-1]
    return [int(p + (l - p) / 2.0)
            for l, p in zip(step["seq_lens"], step["prompt_lens"])]
