"""What the readers of a latent-attention configuration share: the
attention scopes' device seconds in one program, the step's mean contexts,
and the program's own counts of a decode step's expert work (for
`benchmark/peaks_mla.py` to count bytes by).  Every reader returns None
where the program is not latent or does not keep the name or counter."""
from benchmark.metrics._hybrid import experts_touched  # noqa: F401
from benchmark.metrics._program import scope_seconds, step_median

ATTN_SCOPES = ("layer/attn_qkv", "layer/attn", "layer/attn_out")


def is_latent(run):
    return bool(getattr(run.model_cfg, "is_latent", False))


def attn_seconds(run, program):
    """Device self seconds per traced step under the three attention
    scopes of `program`; None where none of them ran there."""
    parts = [scope_seconds(run, program, s) for s in ATTN_SCOPES]
    if not is_latent(run) or all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)


def contexts(step):
    """Every row's mean context over its decode steps."""
    return [p + (l - p) / 2.0
            for l, p in zip(step["seq_lens"], step["prompt_lens"])]


def local_rows(run):
    """(row, choice) pairs of one decode step that fell to experts held
    here, per sparse layer; None without the counters."""
    cfg = run.model_cfg
    n = cfg.n_layers - cfg.first_k_dense
    return step_median(
        run, "pool",
        lambda p: p["moe_rows_local"] / (p["moe_decode_steps"] * n),
    )
