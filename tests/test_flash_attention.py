"""Pallas flash attention vs dense reference: forward + gradients.

Models the reference's CUDA-extension parity tests
(tests/cpp_extensions/test_*.py) — kernel vs python oracle.  Runs the SAME
kernel code in pallas interpret mode on CPU.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.engines import packing
from areal_tpu.ops import attention
from areal_tpu.ops.attention import packed_attention_reference
from areal_tpu.ops.pallas import flash_attention as fa
from areal_tpu.ops.pallas.flash_attention import flash_attention


# How far a result may lie from the plain fp32 reference on the SAME inputs.
# fp32 inputs: the kernels' products are fp32 and only the order of the sums
# differs.  bf16 inputs: the operands reach the MXU as they are, P and dS
# are rounded to bf16 before the product that consumes them, and the result
# is rounded once more on the way out — 2**-9 = 0.2% a rounding, so 2e-2
# (of the largest element, for gradients) is ten roundings' room.
TOL = {"float32": 2e-4, "bfloat16": 2e-2}
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(x):
    return TOL[jnp.dtype(x.dtype).name]


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def _force_trip(monkeypatch, blocks):
    """The kernels' inner loops in trips of at most `blocks` schedule
    blocks (cut to a divisor of the row's blocks, as the chooser's own)."""
    from areal_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(
        fa, "_trip_blocks", lambda n, *_: fa._largest_divisor(n, blocks)
    )


def _inputs(rng, b=2, s=256, hq=4, hkv=2, d=32, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    seg = np.zeros((b, s), np.int32)
    # Row 0: two segments (40% + 30% of s), rest pad; other rows: one full
    # segment.
    a_end, b_end = int(s * 0.4), int(s * 0.7)
    seg[0, :a_end] = 1
    seg[0, a_end:b_end] = 2
    seg[1:, :] = 1
    return q, k, v, jnp.asarray(seg)


class TestFlashForward:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_reference(self, rng, dtype):
        q, k, v, seg = _inputs(rng, dtype=dtype)
        out = flash_attention(q, k, v, seg, block_q=64, block_k=64)
        assert out.dtype == dtype
        ref = packed_attention_reference(*_f32(q, k, v), seg)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=_tol(q), atol=_tol(q),
        )

    @pytest.mark.parametrize("window", [None, 24])
    def test_v_on_zero_columns_is_attention_at_unequal_widths(
            self, rng, window):
        """q/k heads wider than v heads (latent attention's 192 | 128 and
        256 | 128): the kernels take one width, v padded with zero columns
        up to it and the output's first columns kept — exact, with or
        without a band."""
        q, k, v, seg = _inputs(rng, d=32)
        v = v[..., :20]
        vp = jnp.pad(v, ((0, 0),) * 3 + ((0, 12),))
        out = flash_attention(
            q, k, vp, seg, block_q=64, block_k=64, window=window)
        np.testing.assert_array_equal(np.asarray(out[..., 20:]), 0.0)
        hq, hkv = q.shape[2], k.shape[2]
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, jnp.repeat(k, hq // hkv, axis=2)) * 32**-0.5
        at = jnp.arange(q.shape[1])
        mask = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0) & (
            at[None, :, None] >= at[None, None, :])
        if window:
            mask &= at[None, :, None] - at[None, None, :] < window
        p = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
        p = jnp.where(mask.any(-1)[:, None, :, None], p, 0.0)
        want = jnp.einsum(
            "bhqk,bkhd->bqhd", p, jnp.repeat(v, hq // hkv, axis=2))
        np.testing.assert_allclose(
            np.asarray(out[..., :20]), np.asarray(want), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_single_block(self, rng, dtype):
        q, k, v, seg = _inputs(rng, s=128, dtype=dtype)
        out = flash_attention(q, k, v, seg, block_q=128, block_k=128)
        ref = packed_attention_reference(*_f32(q, k, v), seg)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=_tol(q), atol=_tol(q),
        )

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_non_causal(self, rng, dtype):
        q, k, v, seg = _inputs(rng, s=128, dtype=dtype)
        out = flash_attention(q, k, v, seg, causal=False, block_q=64, block_k=64)
        ref = packed_attention_reference(*_f32(q, k, v), seg, causal=False)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=_tol(q), atol=_tol(q),
        )

    def test_padding_rows_zero(self, rng):
        q, k, v, seg = _inputs(rng)
        out = np.asarray(flash_attention(q, k, v, seg, block_q=64, block_k=64))
        assert np.allclose(out[0, int(256 * 0.7):], 0.0, atol=1e-6)

    def test_rejects_unaligned(self, rng):
        q, k, v, seg = _inputs(rng, s=200)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, seg, block_q=128, block_k=128)


def _assert_grads_close(got, want, tol32):
    """fp32 inputs: element for element at `tol32`, as these tests always
    held them; bf16 inputs: within TOL of the gradient's largest element."""
    for a, b, name in zip(got, want, "qkv"):
        if a.dtype == jnp.float32:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=tol32, atol=tol32,
                err_msg=f"d{name}",
            )
            continue
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert np.abs(a - b).max() <= TOL["bfloat16"] * np.abs(b).max(), (
            f"d{name}", np.abs(a - b).max(), np.abs(b).max())


class TestFlashBackward:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_grads_match_reference(self, rng, dtype):
        q, k, v, seg = _inputs(rng, b=1, s=128, hq=2, hkv=1, d=16, dtype=dtype)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, seg, block_q=64, block_k=64)
            o = o.astype(jnp.float32)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = packed_attention_reference(q, k, v, seg)
            return jnp.sum(o * o)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(*_f32(q, k, v))
        assert all(g.dtype == dtype for g in gf)
        _assert_grads_close(gf, gr, 5e-4)

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_grad_multi_segment(self, rng, dtype):
        q, k, v, seg = _inputs(rng, b=2, s=256, hq=2, hkv=2, d=32, dtype=dtype)

        # |o| has a kink at 0: a bf16 o rounded across it flips a whole
        # cotangent, so the bf16 case takes the signs from the reference.
        sign = jnp.sign(packed_attention_reference(*_f32(q, k, v), seg))

        def loss(fn):
            def f(q, k, v):
                o = fn(q, k, v).astype(jnp.float32)
                return jnp.sum(jnp.abs(o) if dtype == jnp.float32 else o * sign)

            return f

        gf = jax.grad(
            loss(lambda q, k, v: flash_attention(q, k, v, seg, block_q=64, block_k=64)),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            loss(lambda q, k, v: packed_attention_reference(q, k, v, seg)),
            argnums=(0, 1, 2),
        )(*_f32(q, k, v))
        _assert_grads_close(gf, gr, 1e-3)


# name -> (S, n_q, n_kv, head_dim, window, sequence lengths): bf16 inputs at
# the head widths, head groupings and bands the cells run.
_BF16_CASES = {
    "gqa_d128": (1024, 4, 2, 128, None, [400, 300, 200]),
    "mha_d256": (512, 2, 2, 256, None, [200, 250]),
    "window_gqa_d128": (1024, 4, 1, 128, 256, [600, 400]),
    "window_d256": (512, 2, 1, 256, 128, [500]),
}


class TestBlockChoice:
    """The three kernels under a block choice (`BlockChoice`: a query sees
    a key where `chosen[q, key_block[k]]`) against the `jnp` form of
    `ops/block_sparse.py`, dense under the mask, on the choice its own
    selection makes: compressed keys, block scores, forced and top blocks
    at toy sizes (kernels of 8 every 4, blocks of 16, 6 a query, selection
    from 128 tokens on).  One row of 512 tokens, 4 / 1 heads of 128: every
    case runs the programs the first compiled."""

    S, N_BLOCKS = 512, 34
    # name -> sequence lengths
    CASES = {
        # one sparse sequence from index 0: a key's block is its index's
        "one_sequence_from_0": [512],
        # a dense short sequence, then a sparse one from index 37: its
        # blocks of 16 keys straddle every tile's edge; padding after
        "sparse_from_37_beside_a_dense_one": [37, 400],
    }

    @staticmethod
    @jax.jit
    def _select(q, k, seg):
        from areal_tpu.ops import block_sparse

        sz = block_sparse.Sizes(
            kernel=8, stride=4, block=16, topk=6, init_blocks=1, window=32,
            dense_len=128)
        return jax.vmap(
            lambda q, k, seg: block_sparse._row_selection(q, k, seg, sz, 128)
        )(q, k, seg)[:2]

    @staticmethod
    @functools.partial(jax.jit, static_argnums=0)
    def _out_and_grads(form, q, k, v, seg, chosen, key_block, w):
        """`form`: "mask", "kernels", or "plain" (the kernels, no choice)."""
        from areal_tpu.ops import block_sparse
        from areal_tpu.ops.pallas.flash_attention import BlockChoice

        def attend(q, k, v):
            if form == "mask":
                return jax.vmap(
                    lambda *row: block_sparse._row_attend_mask(*row, 128)
                )(q, k, v, seg, chosen, key_block)
            choice = BlockChoice(chosen, key_block)
            return flash_attention(
                q, k, v, seg, choice=None if form == "plain" else choice)

        out, vjp = jax.vjp(attend, q, k, v)
        return (out, *vjp(w))

    def _case(self, rng, name):
        q, k, v, _ = _inputs(rng, b=1, s=self.S, hq=4, hkv=1, d=128)
        lens = self.CASES[name]
        seg = jnp.asarray(_packed_row(self.S, lens))
        chosen, key_block = self._select(q, k, seg)
        assert chosen.shape == (1, self.S, 1, self.N_BLOCKS)
        # the choice is one: a dense sequence's queries see every block, a
        # sparse one's 6
        picked = np.asarray(chosen.sum(-1))[0, :, 0]
        assert picked[sum(lens) - 1] == 6
        assert len(lens) == 1 or picked[lens[0] - 1] == self.N_BLOCKS
        real = (seg > 0)[..., None, None]
        w = jnp.asarray(rng.normal(size=q.shape), jnp.float32) * real
        return (q, k, v, seg, chosen, key_block, w), real

    @pytest.mark.parametrize("name", list(CASES))
    def test_forward_and_gradients_match_the_mask_form(self, rng, name):
        args, real = self._case(rng, name)
        want, *want_grads = self._out_and_grads("mask", *args)
        got, *got_grads = self._out_and_grads("kernels", *args)
        np.testing.assert_allclose(
            got, jnp.where(real, want, 0), atol=TOL["float32"])
        _assert_grads_close(got_grads, want_grads, TOL["float32"])

    def test_every_block_chosen_is_the_plain_kernel_bit_for_bit(self, rng):
        args, _ = self._case(rng, "sparse_from_37_beside_a_dense_one")
        every = jnp.ones_like(args[4])
        plain = self._out_and_grads("plain", *args)
        chosen = self._out_and_grads("kernels", *args[:4], every, *args[5:])
        for got, want in zip(chosen, plain):
            np.testing.assert_array_equal(got, want)
        # and the choice is not every block: the selection shows
        selected = self._out_and_grads("kernels", *args)[0]
        assert np.abs(np.asarray(selected - plain[0])).max() > 0.1


class TestBlockCausal:
    """The two-stream BLOCK-causal mask of generation by diffusion over
    blocks (`flash_attention(blocks=(block ids, stream ids))`: a query sees
    the clean keys of EARLIER blocks of its sequence and the keys of its
    own stream and block, those after it in the row too): all three
    kernels, interpreted, against `packed_attention_reference` under the
    dense mask; a block never straddles a tile; the schedule is the causal
    one of the sequences' ids."""

    BLOCK = 4

    def _rows(self, s=512):
        """Two rows of sequences, each (length, first masked block): the
        clean stream padded to the block, the masked stream behind it."""
        def row(seqs):
            seg, blk, stream = (np.zeros(s, np.int32) for _ in range(3))
            off, b = 0, self.BLOCK
            for n, (l, m0) in enumerate(seqs, 1):
                seg[off: off + l] = n
                blk[off: off + l] = np.arange(l) // b
                off += -(-l // b) * b
                m = (-(-l // b) - m0) * b
                seg[off: off + m] = n
                blk[off: off + m] = m0 + np.arange(m) // b
                stream[off: off + m] = 1
                off += m
            return seg, blk, stream

        rows = [row([(70, 3), (45, 0), (130, 20)]), row([(201, 10)])]
        return tuple(jnp.asarray(np.stack(x)) for x in zip(*rows))

    def _operands(self, rng, s=512, dtype=jnp.float32):
        q, k, v, _ = _inputs(rng, b=2, s=s, dtype=dtype)
        return q, k, v

    @pytest.mark.parametrize("trip", [1, 4], ids=["trip128", "trip512"])
    def test_forward_and_gradients_match_the_dense_mask(
            self, rng, monkeypatch, trip):
        _force_trip(monkeypatch, trip)
        seg, blk, stream = self._rows()
        q, k, v = self._operands(rng)
        blocks = (blk, stream)
        got = flash_attention(q, k, v, seg, blocks=blocks)
        want = packed_attention_reference(q, k, v, seg, blocks=blocks)
        np.testing.assert_allclose(got, want, rtol=_tol(q), atol=_tol(q))

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v, seg, blocks=blocks) ** 2).sum()

        g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        w = jax.grad(loss(packed_attention_reference), argnums=(0, 1, 2))(
            q, k, v)
        _assert_grads_close(g, w, 2e-4)

    def test_the_mask_is_the_rule_and_sees_keys_after_the_query(self):
        seg, blk, stream = self._rows()
        mask = np.asarray(attention.make_packed_mask(
            seg, blocks=(blk, stream)))[:, 0]
        seg, blk, stream = (np.asarray(x) for x in (seg, blk, stream))
        for r in range(2):
            same = (seg[r][:, None] == seg[r][None, :]) & (seg[r][:, None] > 0)
            earlier = (stream[r][None, :] == 0) & (
                blk[r][None, :] < blk[r][:, None])
            own = (stream[r][None, :] == stream[r][:, None]) & (
                blk[r][None, :] == blk[r][:, None])
            np.testing.assert_array_equal(mask[r], same & (earlier | own))
        # A clean token sees the rest of its block: keys AFTER it in the row.
        assert mask[0, 0, 3] and not mask[0, 0, 4]
        # A masked token sees no clean token of its own block.
        first_masked = int(np.flatnonzero(stream[0])[0])
        own_clean = np.flatnonzero(
            (seg[0] == seg[0, first_masked]) & (stream[0] == 0)
            & (blk[0] == blk[0, first_masked]))
        assert not mask[0, first_masked, own_clean].any()
        assert mask[0, first_masked, first_masked + 3]

    def test_no_block_straddles_a_tile_and_the_schedule_is_the_causal_one(
            self):
        """Streams start on multiples of the block and a tile is a multiple
        of it, so every visible key after its query lies in the query's own
        tile: the causal schedule of the sequences' ids covers the mask."""
        seg, blk, stream = self._rows()
        mask = np.asarray(attention.make_packed_mask(
            seg, blocks=(blk, stream)))[:, 0]
        t = 128
        sched = fa.live_schedule(seg, t, t, True)
        n = seg.shape[1] // t
        k_lo = np.asarray(sched.k_lo).reshape(2, n)
        k_hi = np.asarray(sched.k_hi).reshape(2, n)
        live = 0
        for r in range(2):
            for qi in range(n):
                tiles = mask[r, qi * t: (qi + 1) * t].reshape(t, n, t)
                has = tiles.any(axis=(0, 2))
                assert not has[qi + 1:].any()  # nothing past the diagonal
                for ki in np.flatnonzero(has):
                    assert k_lo[r, qi] <= ki <= k_hi[r, qi]
                live += int(has.sum())
        counted, grid = packing.flash_tile_counts(np.asarray(seg))
        assert live <= counted <= grid
        starts = np.flatnonzero(np.diff(
            np.asarray(seg[0]) * 2 + np.asarray(stream[0]), prepend=0) != 0)
        assert all(i % self.BLOCK == 0 for i in starts
                   if np.asarray(seg[0])[i] > 0)

    def test_the_codes_hold_sequence_stream_and_block(self):
        seg = jnp.asarray([[0, 1, 1, 16383]])
        blk = jnp.asarray([[0, 0, 65535, 7]])
        stream = jnp.asarray([[0, 0, 1, 1]])
        code = np.asarray(fa.block_codes(seg, blk, stream))
        assert (code >> 17).tolist() == [[0, 1, 1, 16383]]
        assert ((code >> 16) & 1).tolist() == [[0, 0, 1, 1]]
        assert (code & 0xFFFF).tolist() == [[0, 0, 65535, 7]]
        assert (code >= 0).all()

    def test_no_blocks_traces_the_program_it_always_was(self):
        q = jax.ShapeDtypeStruct((1, 256, 4, 32), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, 256, 2, 32), jnp.float32)
        seg = jax.ShapeDtypeStruct((1, 256), jnp.int32)
        plain = jax.jit(flash_attention).lower(q, kv, kv, seg).as_text()
        none = jax.jit(lambda *a: flash_attention(*a, blocks=None)).lower(
            q, kv, kv, seg).as_text()
        both = jax.jit(lambda q, k, v, s: flash_attention(
            q, k, v, s, blocks=(s, s))).lower(q, kv, kv, seg).as_text()
        strip = lambda text: text.split("\n", 1)[1]  # the module's name line
        assert strip(plain) == strip(none) != strip(both)
        with pytest.raises(ValueError, match="window or choice"):
            flash_attention(
                jnp.zeros(q.shape), jnp.zeros(kv.shape), jnp.zeros(kv.shape),
                jnp.ones(seg.shape, jnp.int32), window=4,
                blocks=(jnp.zeros(seg.shape, jnp.int32),) * 2)


class TestTripWidths:
    """The inner loops walk a trip of several schedule blocks; the mask
    inside a trip is exact, so a width moves the order of the sums and
    nothing else.  Every width the chooser can return (1, 2 or 4 blocks of
    128), bf16 inputs, forward and gradients, against the plain fp32
    reference on the same values."""

    @pytest.mark.parametrize("blocks", [1, 2, 4])
    @pytest.mark.parametrize("name", list(_BF16_CASES))
    def test_bf16_matches_reference_at_every_width(
        self, name, blocks, monkeypatch
    ):
        s, hq, hkv, d, window, lens = _BF16_CASES[name]
        _force_trip(monkeypatch, blocks)
        rng = np.random.default_rng(sum(map(ord, name)))
        q, k, v, w = (
            jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.bfloat16)
            for h in (hq, hkv, hkv, hq)
        )
        seg = jnp.asarray(_packed_row(s, lens))

        def loss(fn):
            def f(q, k, v):
                o = fn(q, k, v, seg, window=window)
                return jnp.sum(o.astype(jnp.float32) * w), o

            return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

        (_, o), g = loss(flash_attention)(q, k, v)
        (_, o_ref), g_ref = loss(packed_attention_reference)(*_f32(q, k, v))
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref),
            rtol=TOL["bfloat16"], atol=TOL["bfloat16"],
        )
        _assert_grads_close(g, g_ref, None)

    def test_the_chooser_sees_shapes_alone(self):
        from areal_tpu.ops.pallas.flash_attention import _trip_blocks

        # (row blocks, block, head_dim, backward kernel) -> blocks a trip
        for backward in (False, True):
            assert _trip_blocks(64, 128, 128, backward) == 4  # 8,192 tokens
            assert _trip_blocks(20, 128, 128, backward) == 4  # prefill, 2,560
            assert _trip_blocks(10, 128, 128, backward) == 2  # 4 is no divisor
            assert _trip_blocks(3, 128, 128, backward) == 3
            assert _trip_blocks(1, 64, 32, backward) == 1
        # GLM, qwen3-next: the forward keeps the order of its sums
        assert _trip_blocks(40, 128, 256, False) == 1
        assert _trip_blocks(40, 128, 256, True) == 4


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _packed_row(s, lens, ids=None):
    """One row: sequences of `lens` tokens back to back, padding after."""
    seg = np.zeros((1, s), np.int32)
    off = 0
    for n, l in enumerate(lens):
        seg[0, off:off + l] = ids[n] if ids else n + 1
        off += l
    assert off <= s
    return seg


# name -> (S, n_q, n_kv, head_dim, causal, sequence lengths, ids or None,
#          RESIDENT_BYTES or None)
_SCHEDULE_CASES = {
    # 2,048 tokens of 30-190-token sequences, qwen2-1.5B's 12/2 heads
    "many_short_s2048_gqa12x2": (
        2048, 12, 2, 128, True,
        [61, 187, 30, 122, 95, 160, 44, 178, 133, 70, 190, 88, 149, 52,
         171, 104, 36, 119], None, None),
    "one_segment_fills_s256": (256, 2, 1, 128, True, [256], None, None),
    "one_segment_fills_s2048": (2048, 2, 1, 128, True, [2048], None, None),
    "boundary_inside_a_block_mha": (
        256, 4, 4, 128, True, [200, 56], None, None),
    # real tokens end at 550: q blocks 5..15 are all padding
    "trailing_padding_and_padding_blocks": (
        2048, 2, 1, 128, True, [300, 250], None, None),
    "head_dim_256": (256, 4, 2, 256, True, [100, 60, 50], None, None),
    "non_causal": (512, 2, 1, 128, False, [130, 250, 100], None, None),
    # past the resident limit: K/V in chunks of two tiles, the q side of
    # dkv in chunks of one
    "row_in_chunks": (512, 4, 2, 128, True, [300, 150], None, 300_000),
    "row_in_chunks_non_causal": (
        512, 2, 1, 128, False, [40, 300, 150], None, 300_000),
    # ids out of order: the intervals cover dead tiles, results stand
    "ids_not_monotonic": (
        512, 2, 1, 128, True, [100, 150, 120, 90], [3, 1, 3, 2], None),
}


class TestLiveSchedule:
    """The kernels visit the tiles `live_schedule` names.  A visited tile
    that is fully masked is an exact no-op, so the same kernels under the
    all-tiles schedule must give the same bits."""

    @staticmethod
    def _case(name):
        s, hq, hkv, d, causal, lens, ids, resident = _SCHEDULE_CASES[name]
        rng = np.random.default_rng(sum(map(ord, name)))
        q, k, v, w = (
            jnp.asarray(rng.normal(size=(h, s, d)), jnp.float32)
            for h in (hq, hkv, hkv, hq)
        )
        seg = jnp.asarray(_packed_row(s, lens, ids))
        return q, k, v, w, seg, hq, causal, resident

    @pytest.mark.parametrize("blocks", [1, 2, 4])
    @pytest.mark.parametrize("name", list(_SCHEDULE_CASES))
    def test_live_tiles_equal_all_tiles_bit_for_bit(
        self, name, blocks, monkeypatch
    ):
        """At every trip width: a trip with no live block is as exact a
        no-op as a dead tile was."""
        from areal_tpu.ops.pallas import flash_attention as fa

        q, k, v, do, seg, hq, causal, resident = self._case(name)
        _force_trip(monkeypatch, blocks)
        if resident:
            monkeypatch.setattr(fa, "RESIDENT_BYTES", resident)
        s, d = q.shape[1:]
        blk, scale = 128, d ** -0.5
        n = s // blk

        @jax.jit
        def run(sched):
            o, lse = fa._fwd(q, k, v, seg, sched, hq, scale, blk, blk, causal)
            res = (q, k, v, o, lse, seg, sched)
            return (o, lse) + fa._bwd(scale, blk, blk, causal, res, do)

        live = fa.live_schedule(seg, blk, blk, causal)
        visited = int(jnp.sum(live.k_hi - live.k_lo + 1))
        assert visited == int(jnp.sum(live.q_hi - live.q_lo + 1))
        assert 0 < visited < n * n or n == 1
        got, want = run(live), run(fa.all_tiles_schedule(1, n, n))
        for what, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=what
            )
        # ... and the bits are attention's: the plain reference agrees.
        ref = packed_attention_reference(
            q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
            v.transpose(1, 0, 2)[None], seg, causal=causal,
        )
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(ref[0].transpose(1, 0, 2)),
            rtol=2e-4, atol=2e-4,
        )

    @pytest.mark.parametrize("name", [
        "many_short_s2048_gqa12x2", "one_segment_fills_s256",
        "trailing_padding_and_padding_blocks", "non_causal",
        "ids_not_monotonic",
    ])
    def test_intervals_cover_exactly_the_tiles_with_a_live_element(
        self, name
    ):
        """Against the [S, S] mask itself: every tile with an unmasked
        element is inside its block's interval, and with monotonic ids
        the interval holds nothing else."""
        from areal_tpu.engines.packing import flash_tile_counts
        from areal_tpu.ops.pallas.flash_attention import live_schedule

        s, _, _, _, causal, lens, ids, _ = _SCHEDULE_CASES[name]
        seg = _packed_row(s, lens, ids)
        row = seg[0]
        mask = (row[:, None] == row[None, :]) & (row[:, None] > 0)
        if causal:
            mask &= np.arange(s)[:, None] >= np.arange(s)[None, :]
        n = s // 128
        tiles = mask.reshape(n, 128, n, 128).any(axis=(1, 3))  # [nq, nk]
        sched = live_schedule(jnp.asarray(seg), 128, 128, causal)
        k_lo, k_hi, q_lo, q_hi = (np.asarray(x) for x in sched)
        idx = np.arange(n)
        by_q = (idx[None, :] >= k_lo[:, None]) & (idx[None, :] <= k_hi[:, None])
        by_k = (idx[:, None] >= q_lo[None, :]) & (idx[:, None] <= q_hi[None, :])
        assert (by_q | ~tiles).all() and (by_k | ~tiles).all()
        if ids is None:
            np.testing.assert_array_equal(by_q, tiles)
            np.testing.assert_array_equal(by_k, tiles)
        if causal:  # the host's counter counts the causal schedule
            assert flash_tile_counts(seg) == (int(by_q.sum()), n * n)

    def test_host_counter_sums_rows(self):
        from areal_tpu.engines.packing import flash_tile_counts

        seg = np.concatenate([
            _packed_row(512, [512]),  # 1 + 2 + 3 + 4 tiles
            _packed_row(512, [100, 100]),  # the second crosses a block: 3
            _packed_row(512, []),  # an empty row
        ])
        assert flash_tile_counts(seg) == (13, 48)
        assert flash_tile_counts(seg[:, :64]) == (2, 3)  # rows under a block


class TestFlashSharded:
    """The multi-chip path: shard_map'd kernel on the fake 8-device mesh
    (VERDICT r1 weak #3 'done' criterion — parity vs dense under real
    tp/fsdp layouts)."""

    @pytest.mark.parametrize("layout", ["d2f2m2", "d4m2", "f4m2"])
    def test_matches_reference_on_mesh(self, rng, layout):
        from areal_tpu.base.topology import ParallelConfig, make_mesh
        from areal_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        pc = ParallelConfig.from_str(layout)
        mesh = make_mesh(pc, jax.devices()[: pc.world_size])
        q, k, v, seg = _inputs(rng, b=4, s=256, hq=4, hkv=2, d=32)

        out = jax.jit(
            lambda *a: flash_attention_sharded(*a, mesh=mesh)
        )(q, k, v, seg)
        ref = packed_attention_reference(q, k, v, seg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )

    def test_grads_match_on_mesh(self, rng):
        from areal_tpu.base.topology import ParallelConfig, make_mesh
        from areal_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        pc = ParallelConfig.from_str("d2f2m2")
        mesh = make_mesh(pc, jax.devices()[: pc.world_size])
        q, k, v, seg = _inputs(rng, b=4, s=256, hq=4, hkv=2, d=32)

        def loss_sharded(q, k, v):
            return jnp.sum(
                flash_attention_sharded(q, k, v, seg, mesh) * 0.1
            )

        def loss_ref(q, k, v):
            return jnp.sum(packed_attention_reference(q, k, v, seg) * 0.1)

        gs = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(["dq", "dk", "dv"], gs, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
                err_msg=name,
            )

    def test_rejects_bad_head_split(self, rng):
        from areal_tpu.base.topology import ParallelConfig, make_mesh
        from areal_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        pc = ParallelConfig.from_str("d2m4")
        mesh = make_mesh(pc, jax.devices()[: pc.world_size])
        q, k, v, seg = _inputs(rng, b=4, s=256, hq=4, hkv=2, d=32)
        with pytest.raises(ValueError):
            flash_attention_sharded(q, k, v, seg, mesh)


class TestDecodeAttentionEmptyWindows:
    """Rows whose live window is empty (valid_from >= valid_to) emit exact
    zeros: the XLA form zeroes the softmax of an all-NEG_INF row instead
    of keeping its uniform distribution over garbage.  Parked generation
    slots hit this every step, so a slip here corrupts real decodes."""

    def test_empty_window_rows_zero(self, rng):
        from areal_tpu.ops import attention

        b, s, nq, nkv, d = 4, 128, 8, 2, 128
        q = jnp.asarray(rng.standard_normal((b, 1, nq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, nkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, nkv, d)), jnp.float32)
        lo = jnp.asarray([0, 64, s, 100], jnp.int32)
        hi = jnp.asarray([64, 64, 64, 40], jnp.int32)  # rows 1-3 empty
        empty = np.asarray(lo) >= np.asarray(hi)
        assert empty.tolist() == [False, True, True, True]
        out = np.asarray(attention.decode_attention(q, k, v, lo, hi))
        np.testing.assert_array_equal(out[empty], 0.0)
        assert np.abs(out[~empty]).max() > 0  # live row is real

    def test_empty_window_rows_zero_chunk(self, rng):
        """Chunk form: query i of a row sees [valid_from, valid_to0 + i),
        so a row with valid_from >= valid_to0 + Q - 1 has EVERY query
        fully masked."""
        from areal_tpu.ops import attention

        b, s, Q, nq, nkv, d = 3, 128, 3, 8, 2, 128
        q = jnp.asarray(rng.standard_normal((b, Q, nq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, nkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, nkv, d)), jnp.float32)
        lo = jnp.asarray([0, s, 90], jnp.int32)
        to0 = jnp.asarray([64, 64, 30], jnp.int32)  # rows 1-2: all empty
        empty = np.asarray(lo)[:, None] >= (
            np.asarray(to0)[:, None] + np.arange(Q)[None, :]
        )  # [B, Q]
        assert empty.all(axis=1).tolist() == [False, True, True]
        out = np.asarray(attention.decode_attention_chunk(q, k, v, lo, to0))
        np.testing.assert_array_equal(out[empty], 0.0)
        assert np.abs(out[~empty]).max() > 0


class TestDispatchHidesNothing:
    def test_flash_error_propagates(self, rng, monkeypatch):
        """`use_flash=True` means the kernel: a kernel that cannot run is
        an error, never a quiet fall-through to the dense O(S^2) oracle."""
        from areal_tpu.ops import attention
        from areal_tpu.ops.pallas import flash_attention as fa

        def boom(*a, **k):
            raise NotImplementedError("kernel unavailable")

        monkeypatch.setattr(fa, "flash_attention", boom)
        q, k, v, seg = _inputs(rng, s=64)
        with pytest.raises(NotImplementedError, match="kernel unavailable"):
            attention.packed_attention(q, k, v, seg, use_flash=True)


class TestTPULowering:
    """Lower the kernels a chip can reach for `platforms=["tpu"]` at the
    qwen2-1.5B head geometry (12 q heads, 2 kv heads, d 128).  No TPU is
    needed: lowering already runs Pallas's TPU block-shape checks — the
    guard that would have caught kernels that only ever ran interpreted."""

    N_Q, N_KV, D = 12, 2, 128

    @pytest.fixture(autouse=True)
    def _as_on_tpu(self, monkeypatch):
        # _interpret() follows the platform: compiled kernels, as on a chip.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def _lowered(self, fn, *args):
        text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *args
        ).mlir_module()
        assert "tpu_custom_call" in text  # Mosaic kernel, not interpreted
        return text

    # The benchmark cells' kernel calls, [b * n_q, s, d].
    CELL_SHAPES = {
        "train_row_12x8192x128": (1, 8192, 12, 2, 128),
        "train_rows_24x8192x128": (2, 8192, 12, 2, 128),
        "prefill_192x2560x128": (16, 2560, 12, 2, 128),
        # qwen3_next's gated attention: never 256 before it
        "q3next_16x8192x256": (1, 8192, 16, 2, 256),
        "q7b_per_chip_28x2048x128": (1, 2048, 28, 4, 128),
        # past the resident limit (38,000 tokens of K/V, 34,000 of Q/dO):
        # every kernel's resident side in two chunks
        "row_in_chunks_12x65536x128": (1, 65536, 12, 2, 128),
        # mellum's window layers (32 / 4 heads, a band of 1,024 keys) and
        # GLM's materialised latent attention (20 heads of 256)
        "mellum_window_32x8192x128": (1, 8192, 32, 4, 128, 1024),
        "glm_20x5120x256": (1, 5120, 20, 20, 256),
        # lfm2_moe: heads of 64, half a lane tile, never before it
        "lfm2_32x8192x64": (1, 8192, 32, 8, 64),
        # dots3_note's sliding layers: 8 held heads, q/k 256 with v (128)
        # carried on zero columns, a band of 513 keys over rows of 13,312
        "dots3_window_8x13312x256": (1, 13312, 8, 8, 256, 513),
        # sdar_moe's two-stream rows under the block-causal mask
        "sdar_blocks_32x8192x128": (1, 8192, 32, 4, 128, "blocks"),
    }
    # The trip each cell's FORWARD calls take (`_trip_blocks`), as the scope
    # around the kernel says it (dq's keys and dkv's queries: 512 everywhere).
    FORWARD_TRIPS = {"glm_20x5120x256": 128, "q3next_16x8192x256": 128,
                     "dots3_window_8x13312x256": 128}

    def _cell(self, cell, sharding=None):
        b, s, n_q, n_kv, d, *window = self.CELL_SHAPES[cell]
        window = window[0] if window else None
        blocks = window == "blocks"
        window = None if blocks else window
        q = jax.ShapeDtypeStruct((b, s, n_q, d), jnp.bfloat16,
                                 sharding=sharding)
        kv = jax.ShapeDtypeStruct((b, s, n_kv, d), jnp.bfloat16,
                                  sharding=sharding)
        seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=sharding)

        def attend(q, k, v, seg):
            if blocks:  # the ids ride the id operand: any ids do
                return flash_attention(q, k, v, seg, blocks=(seg, seg % 2))
            return flash_attention(q, k, v, seg, window=window)

        def loss(q, k, v, seg):
            return attend(q, k, v, seg).astype(jnp.float32).sum()

        trip = self.FORWARD_TRIPS.get(cell, 512)
        # in an op_name; a transform wraps the scope: `jvp(keys512)/flash_fwd`
        scopes = [rf"keys{trip}\)*/flash_fwd/", r"keys512\)*/flash_dq/",
                  r"queries512\)*/flash_dkv/"]
        return attend, jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv, seg), scopes

    @pytest.mark.parametrize("cell", list(CELL_SHAPES))
    def test_flash_forward_and_backward(self, cell):
        attend, grad, args, _ = self._cell(cell)
        self._lowered(attend, *args)
        text = self._lowered(grad, *args)
        assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv

    @pytest.mark.parametrize("cell", list(CELL_SHAPES))
    def test_flash_products_take_bf16_and_sum_in_fp32(self, cell):
        """What the three kernels feed the MXU, read from the traced
        kernels (the lowered module keeps a Mosaic kernel as bytecode):
        every product's operands are the inputs' bf16 and its result fp32;
        every `exp`, the softmax statistics and every accumulator fp32."""
        _, grad, args, _ = self._cell(cell)
        kernels = [
            e for e in _eqns(jax.make_jaxpr(grad)(*args).jaxpr)
            if e.primitive.name == "pallas_call"
        ]
        assert [e.params["name"] for e in kernels] == [
            "flash_fwd", "flash_dq", "flash_dkv"]
        for kernel, n_dots in zip(kernels, (2, 3, 4)):
            body = kernel.params["jaxpr"]
            inner = list(_eqns(body))
            dots = [e for e in inner if e.primitive.name == "dot_general"]
            assert len(dots) == n_dots
            for e in dots:
                assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
                assert e.outvars[0].aval.dtype == jnp.float32
            exps = [e for e in inner if e.primitive.name == "exp"]
            assert exps and all(
                e.invars[0].aval.dtype == jnp.float32 for e in exps)
            # the kernel's refs: bf16 are q, k, v, dO and the outputs in
            # their type; ids int32; lse, delta, m, l and the accumulators
            # (the scratch: the last refs) fp32
            kinds = [v.aval.dtype for v in body.invars]
            n_scratch = {"flash_fwd": 3, "flash_dq": 1, "flash_dkv": 2}[
                kernel.params["name"]]
            assert all(k == jnp.float32 for k in kinds[-n_scratch:])

    @pytest.fixture(scope="class")
    def one_chip(self, v5e_chips):
        """A described v5e chip to compile for."""
        from jax.sharding import SingleDeviceSharding

        return SingleDeviceSharding(v5e_chips[0])

    @pytest.fixture
    def _no_persistent_cache(self):
        # A deviceless executable is written to the cache but cannot be
        # read back without a chip: the next run would warn and recompile.
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()

    @pytest.mark.parametrize("cell", list(CELL_SHAPES))
    def test_flash_compiles_for_v5e(self, cell, one_chip, _no_persistent_cache):
        """Mosaic and XLA:TPU for real, at the cells' sizes: lowering does
        not see the VMEM the resident operands take, the compiler does."""
        _, grad, args, scopes = self._cell(cell, one_chip)
        text = jax.jit(grad).lower(*args).compile().as_text()
        for kernel, scope in zip(("flash_fwd", "flash_dq", "flash_dkv"),
                                 scopes):
            assert f"%{kernel}" in text
            # the trip the chooser gave this shape
            assert re.search(scope, text), scope

    def test_flash_under_a_block_choice_compiles_for_v5e(
            self, one_chip, _no_persistent_cache):
        """`sala-docrl8-longctx`'s call — one 13,312-token row, 32 / 2 heads,
        a choice over 210 blocks of 64 keys: beside K/V a step holds the
        keys' one-hot (256 bytes a token) and the q block's four windows."""
        from areal_tpu.ops.pallas.flash_attention import BlockChoice

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        b, s, n_q, n_kv, d, n_blocks = 1, 13312, 32, 2, 128, 210
        q, kv = (sds((b, s, h, d), jnp.bfloat16) for h in (n_q, n_kv))
        ints = sds((b, s), jnp.int32)

        def loss(q, k, v, seg, chosen, key_block):
            return flash_attention(
                q, k, v, seg, choice=BlockChoice(chosen, key_block)
            ).astype(jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv, ints, sds((b, s, n_kv, n_blocks), jnp.bool_), ints
        ).compile().as_text()
        for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
            assert f"%{kernel}" in text

    # The paged attention kernel's calls: (lanes, table columns, pool pages,
    # layers, q heads, kv heads, pool dtype).
    PAGED_SHAPES = {
        "serving_cell_96x3": (96, 3, 192, 28, 12, 2, jnp.bfloat16),
        "serving_cell_int8": (96, 3, 192, 28, 12, 2, jnp.int8),
        "long_window_mp128": (96, 128, 2048, 4, 12, 2, jnp.bfloat16),
        "olmoe_16x16": (96, 3, 192, 3, 16, 16, jnp.bfloat16),
        "q7b_28x4": (96, 3, 192, 8, 28, 4, jnp.bfloat16),
    }

    def _paged_step(self, shape, sharding=None):
        """One layer scan of the serving step's cache path — the new K/V
        scattered into the stacked pool, the kernel reading it at
        (layer, page) — and its arguments' shapes."""
        from areal_tpu.models import transformer as tfm
        from areal_tpu.ops.attention import ragged_paged_attention
        from areal_tpu.ops.pallas.paged_attention import live_page_schedule

        t, mp, n_pool, n_layers, n_q, n_kv, dt = shape
        ps, d = 128, self.D
        quant = dt == jnp.int8

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        pool = sds((n_layers, n_pool, ps, n_kv * d), dt)
        scale = sds((n_layers, n_pool, n_kv, ps) if quant else (0,),
                    jnp.bfloat16)
        new = sds((n_layers, t, n_kv, d), jnp.bfloat16)
        args = (
            tfm.PagedKVCache(pool, pool, scale, scale, ps),
            sds((n_layers, t, n_q, d), jnp.bfloat16), new, new,
            sds((t, mp), jnp.int32), sds((t,), jnp.int32),
            sds((t,), jnp.int32), sds((t,), jnp.int32),
        )

        def step(cache, qs, k_new, v_new, pt, vt, page, off):
            sched = live_page_schedule(pt, vt, n_pool, ps, n_q // n_kv)
            rows, rows_s, stride = tfm._pool_rows(cache, n_kv, page, off)

            def layer(carry, x):
                kc, vc, ksc, vsc, li = carry
                q, k, v = x
                kc, vc, ksc, vsc = tfm._cache_update(
                    kc, vc, ksc, vsc, k, v, li * stride + rows,
                    li * stride * n_kv + rows_s, quant,
                )
                out = ragged_paged_attention(
                    q, kc, vc, li, pt, vt,
                    k_scale=ksc if quant else None,
                    v_scale=vsc if quant else None, schedule=sched,
                )
                return (kc, vc, ksc, vsc, li + 1), out

            return jax.lax.scan(
                layer,
                (cache.k, cache.v, cache.k_scale, cache.v_scale, jnp.int32(0)),
                (qs, k_new, v_new),
            )

        return jax.jit(step, donate_argnums=(0,)), args

    # The static cells whose cache is k/v alone: layers, rows, slots, key
    # heads, query heads a key head, tokens a row and forward.
    KV_SHAPES = {
        "q1p5b-decode-static": (28, 8, 1280, 2, 6, 1),
        "olmoe-decode-tail": (3, 8, 1280, 16, 1, 1),
        "sdar-rollout64-512": (8, 64, 896, 4, 8, 4),
        "q1p5b-train-longprompt": (28, 16, 2816, 2, 6, 1),
    }

    @pytest.mark.parametrize("cell", list(KV_SHAPES))
    def test_kv_decode_loop_compiles_for_v5e(
        self, cell, one_chip, _no_persistent_cache
    ):
        """Mosaic takes `kv_decode` at the cells' geometries, and in a
        decode loop — every layer writes its token into the stacked cache
        and attends — XLA:TPU hands it the cache AS IT LIES: the view
        [L, B, S * n_kv, d] is a bitcast, no layer is sliced out and
        nothing of a cache's or a layer's size is copied or re-laid (as
        XLA ops: a copy of the layer's K and V a call, PERF.md section 7,
        left by PR 68)."""
        from areal_tpu.ops.pallas.kv_decode import kv_decode

        layers, b, s, g, rep, tok = self.KV_SHAPES[cell]
        d, sp = self.D, 256

        def loop(q, k_new, v_new, valid_from):
            kc = jnp.zeros((layers, b, s, g, d), q.dtype)
            vc = jnp.zeros((layers, b, s, g, d), q.dtype)

            def step(state):
                i, kc, vc, acc = state
                slot = sp + i * tok

                def layer(c, li):
                    kc, vc, acc = c
                    kc = jax.lax.dynamic_update_slice(
                        kc, k_new[None], (li, 0, slot, 0, 0))
                    vc = jax.lax.dynamic_update_slice(
                        vc, v_new[None], (li, 0, slot, 0, 0))
                    out = kv_decode(
                        q + acc, kc, vc, li, valid_from, slot + tok)
                    return (kc, vc, out), None

                kc, vc, acc = jax.lax.scan(
                    layer, (kc, vc, acc), jnp.arange(layers))[0]
                return i + 1, kc, vc, acc

            return jax.lax.while_loop(
                lambda st: st[0] < (s - sp) // tok, step,
                (0, kc, vc, jnp.zeros_like(q)))[3]

        def placed(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        compiled = jax.jit(loop).lower(
            placed((b, tok, g * rep, d)), placed((b, tok, g, d)),
            placed((b, tok, g, d)), placed((b,), jnp.int32)).compile()
        text = compiled.as_text()
        assert "%kv_decode" in text
        sizes = (
            f"[{layers},{b},{s},{g},{d}]", f"[1,{b},{s},{g},{d}]",
            f"[{b},{s},{g},{d}]", f"[{layers},{b},{s * g},{d}]",
            f"[{b},{s * g},{d}]")
        moved = [
            line.strip()[:200] for line in text.splitlines()
            if any(x in line.split(" = ")[-1].split("(")[0] for x in sizes)
            and any(op in line for op in (
                " copy(", " transpose(", " reshape(", " dynamic-slice("))
        ]
        assert not moved, moved[:2]
        # Two caches and nothing of their size beside them.
        cache_bytes = layers * b * s * g * d * 2
        assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * cache_bytes

    @pytest.mark.parametrize("cell", list(PAGED_SHAPES))
    def test_paged_attention_kernel(self, cell):
        fn, args = self._paged_step(self.PAGED_SHAPES[cell])
        self._lowered(fn, *args)

    @pytest.mark.parametrize("cell", list(PAGED_SHAPES))
    def test_paged_attention_compiles_for_v5e(
        self, cell, one_chip, _no_persistent_cache
    ):
        """Mosaic takes the kernel at every geometry, and XLA:TPU hands it
        the stacked pool AS IT LIES: no copy or re-layout of the pool
        around the scatter or the call (PERF.md, PR 37), whose temporaries
        would be whole pools."""
        shape = self.PAGED_SHAPES[cell]
        fn, args = self._paged_step(shape, one_chip)
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        assert "%ragged_paged" in text
        _, _, n_pool, n_layers, _, n_kv, dt = shape
        pool = f"[{n_layers},{n_pool},128,{n_kv * self.D}]"
        copies = [
            line for line in text.splitlines()
            if pool in line.split(" = ")[-1].split("(")[0]
            and " copy(" in line
        ]
        assert not copies, copies[:2]
        layer_bytes = n_pool * 128 * n_kv * self.D * jnp.dtype(dt).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
