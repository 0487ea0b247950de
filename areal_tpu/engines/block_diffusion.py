"""The static decode program's loop over BLOCKS: generation by diffusion
over blocks (`ModelConfig.block_length` B > 0; BD3-LM, arXiv:2503.09573;
SDAR's `block_diffusion_generate`, arXiv:2510.06303).

Where the token loop of `GeneratorEngine._get_gen_fn` yields one token a
row and iteration, an iteration here yields a BLOCK of B tokens a row:

  * the prompt's whole blocks (B floor(P / B) tokens) are prefilled under
    the block-causal mask, with no head, to END at the bucket `sp` (a
    multiple of B); the prompt's tail (P mod B tokens) is carried into the
    row's first block.  Blocks are by ABSOLUTE position, so block k of
    every row lies at the common slots [sp + kB, sp + kB + B);
  * a block starts as its tail followed by the mask token M.  Denoising
    step s: one forward of the block's B tokens against the cache
    (`transformer.block_step`), a draw and its confidence at every masked
    place (`sampling.draw_with_confidence`), and the B / T masked places
    of largest confidence revealed (`sampling.reveal_by_confidence`; the
    remainder of B / T to the earliest steps): T =
    `cfg.denoising_forwards` forwards a block, the rows in lockstep.  It
    is the family's `low_confidence_static`; its rule by a confidence
    threshold, whose steps a block follow the draws, is not built;
  * ONE commit forward of the now clean block, without the head, leaves
    the block's k/v in the cache (a denoising forward writes the same
    slots with its part-masked block's, which no later step reads);
  * a row is done at the first EOS of a committed block; tokens past it,
    and past `max_new_tokens` in the last block, are dropped.

The log-probability a row returns for its token j is `l_j = log
softmax(head(y_j))[x_j]`, y_j the model's output at position j with EVERY
place of j's block holding M and every earlier block clean — a function of
the tokens alone, which the trainer's two-stream forward recomputes
(`engines/packing.py`).  For every block after a row's first, step 0's
input IS that state; a row's first block holds its prompt's tail, so the
program makes one more forward a call with the tail masked too
(`gen/bd_first_block_logp`).

Uniforms: step s of block k draws `uniform(fold_in(fold_in(key, k), s),
[rows, B])` — a reference handed the same key replays the trajectory.
"""

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models import transformer as tfm
from areal_tpu.models.branches import LoopStep
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops.sampling import draw_with_confidence, reveal_by_confidence


def reveals(cfg: ModelConfig) -> Tuple[int, ...]:
    """Places a block's denoising steps reveal, step by step: B / T, the
    remainder to the earliest steps."""
    blk, t = cfg.block_length, cfg.denoising_forwards
    return tuple(blk // t + (s < blk % t) for s in range(t))


def refuse(cfg: ModelConfig, g) -> None:
    """What the block loop does not build, by name."""
    if g.min_new_tokens > 0:
        raise tfm.BlockLayoutError(
            "min_new_tokens with block_length > 0: the block loop has no "
            "step to hold EOS back at")
    if g.spec_decode_k > 0 or g.stop:
        raise tfm.BlockLayoutError(tfm._BLOCK_REFUSAL.serving)


def split_prompts(cfg: ModelConfig, prompts, sp: int, pad_id: int, b: int):
    """`prompts` (token arrays) -> (whole blocks right-aligned to `sp`
    [b, sp], their lengths [b], the tails [b, B] and their lengths [b])."""
    blk = cfg.block_length
    whole_tok = np.full((b, sp), pad_id, np.int32)
    whole_len = np.zeros((b,), np.int32)
    tail_tok = np.full((b, blk), cfg.mask_token_id, np.int32)
    tail_len = np.zeros((b,), np.int32)
    for r, toks in enumerate(prompts):
        toks = np.asarray(toks, np.int32)
        n = len(toks) // blk * blk
        if n:
            whole_tok[r, sp - n:] = toks[:n]
        whole_len[r] = n
        tail_tok[r, : len(toks) - n] = toks[n:]
        tail_len[r] = len(toks) - n
    return whole_tok, whole_len, tail_tok, tail_len


def n_blocks(cfg: ModelConfig, max_new: int, tail_len) -> int:
    """Blocks the program steps: what the row with the longest tail needs
    for `max_new` tokens (a row with a shorter tail is done a block
    sooner, or drops the last block's spare places)."""
    return -(-(int(max(tail_len, default=0)) + max_new) // cfg.block_length)


COUNTERS = (
    "blocks", "denoise_forwards", "commit_forwards", "first_block_forwards",
    "tokens_kept", "tokens_dropped",
)


def build(engine, b: int, sp: int, s_total: int, nb: int, g, with_cache):
    """The jitted block program of one shape: f(params, whole_tok,
    whole_len, tail_tok, tail_len, key) -> (tokens [b, max_new], log-probs,
    generated lengths [b], the MoE counters' sums, the block counters
    [len(COUNTERS) + B]: `COUNTERS` then the places revealed by step, the
    step that revealed each token [b, max_new]) + (the cache,)."""
    cfg = engine.cfg
    blk, mask_id, eos = cfg.block_length, cfg.mask_token_id, engine.eos_token_id
    max_new = g.max_new_tokens
    steps = cfg.denoising_forwards
    n_reveal = jnp.asarray(reveals(cfg), jnp.int32)
    in_place = engine._expert_leaves_in_place
    expert_kernel = engine._expert_kernel
    row_kernel = engine._row_kernel
    counters = tfm.decode_counters(cfg)
    wave = engine._prefill_wave_rows(b, sp)
    dtype = engine.compute_dtype
    use_flash = engine._use_flash

    def forward(params, x, pos, cache, slot, valid_from, sums, head=True):
        logits, cache, given = tfm.block_step(
            params, cfg, x, pos, cache, slot, valid_from, head=head,
            experts_in_place=in_place, expert_kernel=expert_kernel,
            row_kernel=row_kernel)
        at = LoopStep(slot, valid_from, cache, x.shape[0] * blk)
        sums = {
            name: sums[name] + counter.step(given.get(name), cfg, at)
            for name, counter in counters.items()
        }
        return logits, cache, sums

    def prefill(params, tok, seg, cache):
        bsz = tok.shape[0]
        if wave == bsz:
            return tfm.prefill(
                params, cfg, tok, seg, cache, use_flash=use_flash,
                head=False)[1]
        waves = bsz // wave

        def one(cache, xs):
            i, tk, sg = xs
            part = tfm.init_kv_cache(cfg, wave, cache.s_max, dtype=dtype)
            _, part = tfm.prefill(
                params, cfg, tk, sg, part, use_flash=use_flash, head=False)
            return jax.tree.map(
                lambda whole, new: jax.lax.dynamic_update_slice_in_dim(
                    whole, new, i * wave, axis=1), cache, part), None

        return jax.lax.scan(one, cache, (
            jnp.arange(waves), tok.reshape(waves, wave, sp),
            seg.reshape(waves, wave, sp)))[0]

    @jax.jit
    def gen(params, whole_tok, whole_len, tail_tok, tail_len, key):
        bsz = whole_tok.shape[0]
        seg = (
            jnp.arange(sp)[None, :] >= (sp - whole_len)[:, None]
        ).astype(jnp.int32)
        valid_from = sp - whole_len
        cache = tfm.init_kv_cache(cfg, bsz, s_total, dtype=dtype)
        cache = prefill(params, whole_tok, seg, cache)
        place = jnp.arange(blk, dtype=jnp.int32)[None, :]
        pos0 = whole_len[:, None] + place  # the first block's positions
        is_tail = place < tail_len[:, None]
        all_masked = jnp.full((bsz, blk), mask_id, jnp.int32)
        sums = {
            name: jnp.zeros((counter.width(cfg, bsz * blk),), jnp.float32)
            for name, counter in counters.items()
        }
        # The first block with its tail masked too: the state its tokens'
        # log-probs are defined under (the cache keeps nothing of it).
        with jax.named_scope("gen/bd_first_block_logp"):
            logits, cache, sums = forward(
                params, all_masked, pos0, cache, sp, valid_from, sums)
            lsm_first = jax.nn.log_softmax(logits, axis=-1)
        rows = jnp.arange(bsz)[:, None]

        def denoise(params, k, s, x, masked, cache, sums, hist, step_of):
            """One denoising step of block k -> what it left."""
            slot, pos = sp + k * blk, pos0 + k * blk
            with jax.named_scope("gen/bd_denoise"):
                logits, cache, sums = forward(
                    params, x, pos, cache, slot, valid_from, sums)
            with jax.named_scope("gen/bd_unmask"):
                u = jax.random.uniform(
                    jax.random.fold_in(jax.random.fold_in(key, k), s),
                    (bsz, blk), jnp.float32)
                tok, conf = draw_with_confidence(
                    logits.reshape(bsz * blk, -1), u.reshape(-1),
                    temperature=g.temperature, top_k=g.top_k, top_p=g.top_p,
                    greedy=g.greedy)
                reveal = reveal_by_confidence(
                    conf.reshape(bsz, blk), masked, n_reveal[s])
                x = jnp.where(reveal, tok.reshape(bsz, blk), x)
                hist = hist.at[s].add(jnp.sum(reveal).astype(jnp.float32))
                step_of = jnp.where(reveal, s, step_of)
            return logits, x, masked & ~reveal, cache, sums, hist, step_of

        def block(state):
            (k, done, gen_len, out_toks, out_logps, out_step, cache, sums,
             hist, n) = state
            first = k == 0
            x = jnp.where(first & is_tail, tail_tok, mask_id)
            masked = ~(first & is_tail)
            step_of = jnp.zeros((bsz, blk), jnp.int32)
            # Step 0 stands outside the loop: its input is the all-masked
            # block whose log-softmax the block's tokens are scored under.
            logits, x, masked, cache, sums, hist, step_of = denoise(
                params, k, 0, x, masked, cache, sums, hist, step_of)
            lsm = jnp.where(
                first, lsm_first, jax.nn.log_softmax(logits, axis=-1))

            def step(s, c):
                return tuple(denoise(params, k, s, *c)[1:])

            # The steps reveal B places between them: none stays masked.
            x, _, cache, sums, hist, step_of = jax.lax.fori_loop(
                1, steps, step, (x, masked, cache, sums, hist, step_of))
            with jax.named_scope("gen/bd_commit"):
                _, cache, sums = forward(
                    params, x, pos0 + k * blk, cache, sp + k * blk,
                    valid_from, sums, head=False)
            logp = jnp.take_along_axis(lsm, x[..., None], axis=-1)[..., 0]
            at = k * blk + place - tail_len[:, None]  # place among the new
            made = (at >= 0) & ~(first & is_tail)
            is_eos = made & (x == eos)
            after_eos = (jnp.cumsum(is_eos, axis=1) - is_eos) > 0
            keep = made & (at < max_new) & ~done[:, None] & ~after_eos
            to = jnp.where(keep, at, max_new)  # past the end: dropped
            out_toks = out_toks.at[rows, to].set(x, mode="drop")
            out_logps = out_logps.at[rows, to].set(logp, mode="drop")
            out_step = out_step.at[rows, to].set(step_of, mode="drop")
            gen_len = gen_len + jnp.sum(keep, axis=1).astype(jnp.int32)
            done = done | jnp.any(is_eos & keep, axis=1) | (gen_len >= max_new)
            kept = jnp.sum(keep).astype(jnp.float32)
            n = n + jnp.stack([  # in `COUNTERS`' order
                1.0, float(steps), 1.0, 0.0, kept,
                jnp.sum(made).astype(jnp.float32) - kept,
            ])
            return (k + 1, done, gen_len, out_toks, out_logps, out_step,
                    cache, sums, hist, n)

        def cond(state):
            k, done, *_ = state
            return (k < nb) & ~jnp.all(done)

        state = jax.lax.while_loop(cond, block, (
            jnp.int32(0), jnp.zeros((bsz,), bool),
            jnp.zeros((bsz,), jnp.int32),
            jnp.zeros((bsz, max_new), jnp.int32),
            jnp.zeros((bsz, max_new), jnp.float32),
            jnp.zeros((bsz, max_new), jnp.int32), cache, sums,
            jnp.zeros((blk,), jnp.float32),
            jnp.asarray([0.0, 0.0, 0.0, 1.0, 0.0, 0.0], jnp.float32),
        ))
        (_, _, gen_len, out_toks, out_logps, out_step, cache, sums, hist,
         n) = state
        return (out_toks, out_logps, gen_len, sums,
                jnp.concatenate([n, hist]), out_step) + (
            (cache,) if with_cache else ())

    return gen


def report(cfg: ModelConfig, counts: np.ndarray, rows: int) -> dict:
    """A generate call's block counters (`build`'s fifth output, summed
    over its chunks; `rows` the rows they stepped) as `last_pool_stats`
    keys: `bd/blocks`, `bd/denoise_forwards`, `bd/commit_forwards`
    (forwards of the whole batch, one a row in lockstep), `bd/tokens_kept`
    and `bd/tokens_dropped` (past EOS or `max_new_tokens`),
    `bd/tokens_per_forward` (kept a row over every forward, the first
    block's log-prob forward with them: 4/3 at B 4, T 2),
    `bd/revealed_by_step` (places revealed at each step, a block and row)."""
    n = dict(zip(COUNTERS, (float(c) for c in counts)))
    forwards = (
        n["denoise_forwards"] + n["commit_forwards"]
        + n["first_block_forwards"])
    per = max(n["blocks"] * rows, 1.0)
    return {
        "bd/blocks": n["blocks"],
        "bd/denoise_forwards": n["denoise_forwards"],
        "bd/commit_forwards": n["commit_forwards"],
        "bd/first_block_forwards": n["first_block_forwards"],
        "bd/forwards": forwards,
        "bd/tokens_kept": n["tokens_kept"],
        "bd/tokens_dropped": n["tokens_dropped"],
        "bd/tokens_per_forward": n["tokens_kept"] / max(forwards * rows, 1.0),
        "bd/revealed_by_step": [
            float(c) / per for c in counts[len(COUNTERS):]],
        "bd/block_length": cfg.block_length,
        "bd/denoising_steps": cfg.denoising_forwards,
    }
