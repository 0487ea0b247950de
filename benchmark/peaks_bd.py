"""FLOP and byte arithmetic of GENERATION BY DIFFUSION OVER BLOCKS (sdar_moe:
full softmax-attention layers with the rank's share of gated experts behind
each, `cfg.block_length` B tokens a decode step).  `benchmark/peaks.py`
counts a generator that yields one token a row and iteration and a trainer
whose stack runs over the batch's own tokens; the metrics of this family
divide by what this file counts:

  * a FORWARD OF THE BLOCK LOOP (a denoising step, the commit, the first
    block's log-prob forward) puts B tokens a row through every layer —
    B queries a row over the row's context and its own block — and, unless
    it is a commit, through the head.  A block costs T denoising forwards
    and one commit (`forwards_of`).
  * the TRAINER's stack runs over STREAM slots: a sequence of L tokens
    whose last R want a log-prob lies as L clean slots and B ceil-blocks
    of masked ones (`stream_slots`); the head reads the wanted tokens'
    rows alone.  Visible pairs under the two-stream mask: a clean query at
    position p sees the B floor(p / B) + B tokens of its blocks up to its
    own, a masked query the same count (its own block's masked tokens in
    the clean ones' place) — `stream_pairs`.

A multiply-add is 2 FLOPs; forward + backward = 3 x forward, recompute
excluded, as everywhere in this benchmark.
"""

from benchmark.peaks_hybrid import BF16, mlp_params, moe_layer_parts
from benchmark.peaks_ssm import attn_params

FP32 = 4


def layer_params(cfg):
    """Parameters in ONE token's matmuls of one layer (the experts at the
    rank's share of a token's choices)."""
    return attn_params(cfg) + mlp_params(cfg)


def head_params(cfg):
    return cfg.hidden_dim * cfg.vocab_size


def matmul_params(cfg):
    return cfg.n_layers * layer_params(cfg) + head_params(cfg)


def pair_flops(cfg, pairs):
    """QK^T and PV over `pairs` (query, key) pairs, every layer."""
    return 4.0 * cfg.n_q_heads * cfg.head_dim * cfg.n_layers * float(pairs)


def forwards_of(cfg, prompt_len, gen_len):
    """(blocks, denoising forwards, commits) a row of `prompt_len` prompt
    tokens takes for `gen_len` new ones, under the static rule."""
    blk = cfg.block_length
    blocks = -(-(prompt_len % blk + gen_len) // blk)
    return blocks, blocks * cfg.denoising_forwards, blocks


# ------------------------------------------------------------------ generate


def flops_generate(cfg, prompt_lens, gen_lens):
    """The block program's FLOPs: the prompts' whole blocks prefilled with
    no head (block-causal: a token sees its blocks up to its own), one
    log-prob forward of the first block a row, and T + 1 forwards a block
    of B tokens over the row's context, T of them through the head."""
    blk, total = cfg.block_length, 0.0
    layers = 2.0 * cfg.n_layers * layer_params(cfg)
    head = 2.0 * head_params(cfg)
    for p, g in zip(prompt_lens, gen_lens):
        whole = p // blk * blk
        total += layers * whole + pair_flops(
            cfg, sum(b + blk for b in range(0, whole, blk)) * blk)
        blocks, denoise, commits = forwards_of(cfg, p, g)
        n_fwd, n_head = denoise + commits + 1, denoise + 1
        total += blk * (layers * n_fwd + head * n_head)
        # B queries over the context and the block, every forward.
        ctx = sum(whole + k * blk + blk for k in range(blocks))
        total += pair_flops(cfg, blk * (
            ctx * (cfg.denoising_forwards + 1) + whole + blk))
    return total


def forward_bytes(cfg, context_lens, experts_touched=None, local_rows=None,
                  head=True):
    """HBM bytes ONE forward of the block loop over these rows has to
    move: every layer's attention weights once, the K and V of each row's
    context and block (read) and its block (written), the experts the
    rows' B tokens touch (`moe_layer_parts` over rows x B tokens), and —
    a denoising forward — the head and the rows' fp32 logits."""
    blk, rows = cfg.block_length, len(context_lens)
    per_slot = 2 * cfg.n_kv_heads * cfg.head_dim * BF16
    kv = (float(sum(context_lens)) + 2 * blk * rows) * per_slot
    tokens = rows * blk
    mlp = sum(by for _, by in moe_layer_parts(
        cfg, tokens, experts_touched, local_rows).values())
    out = cfg.n_layers * (attn_params(cfg) * BF16 + kv + mlp)
    if head:
        out += head_params(cfg) * BF16 + tokens * cfg.vocab_size * FP32
    return out


# --------------------------------------------------------------------- train


def stream_slots(cfg, seq_len, prompt_len):
    """(clean, masked) slots of one trained sequence: its tokens, and B
    mask tokens a block from the first response token's block on."""
    blk = cfg.block_length
    first = prompt_len // blk
    last = (seq_len - 1) // blk
    return seq_len, (last - first + 1) * blk


def stream_pairs(cfg, seq_len, prompt_len):
    """(query, key) pairs the two-stream mask keeps for one sequence."""
    blk = cfg.block_length

    def seen(p):  # the tokens of position p's blocks up to its own
        return min((p // blk + 1) * blk, -(-seq_len // blk) * blk)

    clean = sum(min(seen(p), seq_len) for p in range(seq_len))
    first = prompt_len // blk * blk
    _, masked = stream_slots(cfg, seq_len, prompt_len)
    return clean + sum(
        min(p // blk * blk, seq_len) + blk for p in range(first, first + masked))


def flops_train(cfg, seq_lens, prompt_lens):
    """Forward + backward of a train step over these sequences: the layers
    over both streams' slots, the visible pairs, the head over the
    response's tokens."""
    total = 0.0
    for s, p in zip(seq_lens, prompt_lens):
        clean, masked = stream_slots(cfg, s, p)
        total += 2.0 * cfg.n_layers * layer_params(cfg) * (clean + masked)
        total += pair_flops(cfg, stream_pairs(cfg, s, p))
        total += 2.0 * head_params(cfg) * (s - p)
    return 3.0 * total
