"""Paged KV cache tests: the compile-once / zero-copy serving contract.

The serving plane (engines/generator.py + engines/paging.py +
models/transformer.py PagedKVCache) must produce the STATIC decode
program's greedy tokens (the static program itself is pinned to
`tfm.forward`'s argmax by tests/test_generator.py), while compiling its
chunk program exactly once per generate call and copying zero cache
bytes.  int8 pools are pinned to themselves across chunk geometries
(quantise once, so chunk boundaries cannot move the numerics).  Page
recycling and pool exhaustion round out the allocator contract.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines.generator import GeneratorEngine
from areal_tpu.engines.paging import PageAllocator, PagePoolExhausted
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config

EOS = 7


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def params(cfg):
    return tfm.init_params(cfg, jax.random.PRNGKey(11))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])


def _prompt_sample(rng, cfg, lens):
    data = np.concatenate(
        [rng.integers(8, cfg.vocab_size, size=l) for l in lens]
    ).astype(np.int32)
    return SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(len(lens))],
        seqlens={"packed_prompts": [[l] for l in lens]},
        data={"packed_prompts": data},
    )


def _engine(cfg, params, mesh, **kw):
    kw.setdefault("kv_page_size", 8)
    return GeneratorEngine(cfg, params, mesh, eos_token_id=EOS, **kw)


def _static_and_serving(eng, sample, g, g_static=None):
    """The same requests through both programs of ONE engine: the static
    decode program is the reference, the serving plane the subject."""
    ref = eng.generate(
        sample, MicroBatchSpec(), g_static or g, inflight=False
    )
    out = eng.generate(sample, MicroBatchSpec(), g, inflight=True)
    return ref, out


def _assert_same_tokens(a, b):
    assert a.seqlens["packed_input_ids"] == b.seqlens["packed_input_ids"]
    np.testing.assert_array_equal(
        np.asarray(a.data["packed_input_ids"]),
        np.asarray(b.data["packed_input_ids"]),
    )


def _assert_same_output(a, b):
    assert a.seqlens["packed_input_ids"] == b.seqlens["packed_input_ids"]
    np.testing.assert_array_equal(
        np.asarray(a.data["packed_input_ids"]),
        np.asarray(b.data["packed_input_ids"]),
    )
    np.testing.assert_allclose(
        np.asarray(a.data["packed_logprobs"]),
        np.asarray(b.data["packed_logprobs"]),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_array_equal(
        np.asarray(a.data["seq_no_eos_mask"]),
        np.asarray(b.data["seq_no_eos_mask"]),
    )


class TestPageAllocator:
    def test_reserve_appends_without_moving(self):
        a = PageAllocator(n_pages=8, page_size=4, n_slots=2, max_pages=4)
        a.reserve(0, 5)  # 2 pages
        first = a.table[0, :2].copy()
        a.reserve(0, 9)  # grow to 3 — existing mappings must not move
        np.testing.assert_array_equal(a.table[0, :2], first)
        assert a.used[0] == 3
        assert a.allocated_pages() == 3

    def test_release_recycles(self):
        a = PageAllocator(n_pages=4, page_size=4, n_slots=2, max_pages=4)
        a.reserve(0, 16)  # whole pool
        assert not a.can_reserve(1, 1)
        a.release(0)
        assert a.used[0] == 0 and (a.table[0] == a.sentinel).all()
        a.reserve(1, 16)
        assert a.pages_recycled == 4

    def test_pool_exhaustion_message(self):
        a = PageAllocator(n_pages=2, page_size=4, n_slots=2, max_pages=8)
        a.reserve(0, 8)
        with pytest.raises(PagePoolExhausted, match="page pool exhausted"):
            a.reserve(1, 4)
        # Failed reserve left state untouched.
        assert a.used[1] == 0 and a.allocated_pages() == 2

    def test_table_width_overflow(self):
        a = PageAllocator(n_pages=16, page_size=4, n_slots=1, max_pages=2)
        with pytest.raises(PagePoolExhausted, match="max_pages"):
            a.reserve(0, 12)


INT8_GEOMETRIES = [(w, ps) for w in (1, 4, 16) for ps in (4, 8)]


class TestPagedParity:
    """Token-for-token greedy parity against the static program, over
    slot retirement + re-admission (5 requests, 2 slots)."""

    LENS = (4, 11, 6, 9, 5)

    def _run(self, cfg, params, mesh, rng, g, g_static=None, **kw):
        eng = _engine(cfg, params, mesh, max_decode_batch=2, **kw)
        sample = _prompt_sample(rng, cfg, self.LENS)
        ref, out = _static_and_serving(eng, sample, g, g_static)
        _assert_same_output(ref, out)
        assert eng.decode_compiles == 1
        assert eng.cache_copy_bytes == 0
        return eng

    def _int8_self_identity(self, cfg, params, mesh, rng, k):
        """Quantise once: fresh KV is quantised when first written and
        every later read sees the stored codes, so the slice width W and
        the page size — which only move chunk and page boundaries —
        cannot change an int8 pool's greedy tokens."""
        sample = _prompt_sample(rng, cfg, self.LENS)
        g = GenerationHyperparameters(
            n=1, max_new_tokens=10, greedy=True, spec_decode_k=k
        )
        outs = {}
        for w, ps in INT8_GEOMETRIES:
            eng = _engine(
                cfg, params, mesh, max_decode_batch=2,
                kv_cache_dtype="int8", prefill_chunk_tokens=w,
                kv_page_size=ps,
            )
            outs[(w, ps)] = eng.generate(
                sample, MicroBatchSpec(), g, inflight=True
            )
            assert eng.decode_compiles == 1
            assert eng.prefill_dispatches == 0
        first = outs[INT8_GEOMETRIES[0]]
        for geom in INT8_GEOMETRIES[1:]:
            _assert_same_output(first, outs[geom])
        return first

    def test_plain_greedy(self, cfg, params, mesh, rng):
        g = GenerationHyperparameters(n=1, max_new_tokens=8, greedy=True)
        self._run(cfg, params, mesh, rng, g)

    def test_plain_greedy_int8(self, cfg, params, mesh, rng):
        self._int8_self_identity(cfg, params, mesh, rng, k=0)

    def test_spec_greedy(self, cfg, params, mesh, rng):
        """Greedy speculation is the argmax chain whatever the draft
        grouping: the spec rows of the serving chunk reproduce the
        static program's (non-speculative) tokens."""
        g = GenerationHyperparameters(
            n=1, max_new_tokens=10, greedy=True, spec_decode_k=2
        )
        gs = GenerationHyperparameters(n=1, max_new_tokens=10, greedy=True)
        self._run(cfg, params, mesh, rng, g, g_static=gs)

    def test_spec_greedy_int8(self, cfg, params, mesh, rng):
        self._int8_self_identity(cfg, params, mesh, rng, k=2)

    def test_paged_pallas_kernel_parity(
        self, cfg, params, mesh, rng, monkeypatch
    ):
        """The serving chunk through the Pallas paged attention kernel
        (what a TPU backend takes; interpret mode on CPU) — same greedy
        tokens as the static program on the XLA form."""
        g = GenerationHyperparameters(n=1, max_new_tokens=8, greedy=True)
        eng = _engine(cfg, params, mesh, max_decode_batch=2)
        sample = _prompt_sample(rng, cfg, self.LENS)
        ref = eng.generate(sample, MicroBatchSpec(), g, inflight=False)
        monkeypatch.setattr(
            GeneratorEngine, "_paged_kernel", property(lambda self: True)
        )
        out = eng.generate(sample, MicroBatchSpec(), g, inflight=True)
        _assert_same_output(ref, out)
        assert eng.decode_compiles == 1


class TestCompileOnceContract:
    def test_long_decode_compiles_once_copies_nothing(
        self, cfg, params, mesh, rng
    ):
        """A 160-token decode maps new pages as rows lengthen: exactly
        one compilation and zero copied cache bytes, for the static
        program's tokens."""
        eng = _engine(cfg, params, mesh, max_decode_batch=2)
        sample = _prompt_sample(rng, cfg, (6, 9))
        # min_new == max_new masks EOS: rows decode the whole budget.
        g = GenerationHyperparameters(
            n=1, max_new_tokens=160, min_new_tokens=160, greedy=True
        )
        ref, out = _static_and_serving(eng, sample, g)
        _assert_same_output(ref, out)
        assert eng.decode_compiles == 1
        assert eng.cache_copy_bytes == 0
        assert eng.last_pool_stats["peak_pages_used"] >= 2 * (160 // 8)

    def test_pool_stats_reported(self, cfg, params, mesh, rng):
        paged = _engine(cfg, params, mesh, max_decode_batch=2)
        sample = _prompt_sample(rng, cfg, (5, 8, 6))
        g = GenerationHyperparameters(n=1, max_new_tokens=6, greedy=True)
        paged.generate(sample, MicroBatchSpec(), g, inflight=True)
        st = paged.last_pool_stats
        assert st["kind"] == "paged"
        assert st["page_size"] == 8
        assert 0.0 < st["utilization"] <= 1.0
        assert st["peak_pages_used"] <= st["pool_pages"]


class TestPageRecycling:
    @pytest.mark.parametrize(
        "lens,n",
        [((4, 11, 6, 9, 5, 7), 1), ((11, 4, 9), 2)],
        ids=["singles", "groups"],
    )
    def test_bounded_pool_recycles_and_matches(
        self, cfg, params, mesh, rng, lens, n
    ):
        """A pool too small for all slots at once: retirement must
        recycle pages into later admissions (throttling them, never
        corrupting them) — outputs still match the static program.  In
        groups the 11-token owner's follower is passed over and the
        request behind it does not fit the pool: that still ends the
        round (first come, first served under memory pressure)."""
        paged = _engine(
            cfg, params, mesh, kv_pool_pages=4, max_decode_batch=2
        )
        # Worst case per slot: ceil((11 + 8 + 8) / 8) = 4 pages — the
        # pool holds exactly ONE slot's worst case, so the second slot
        # waits for the first to retire (admission against the budget).
        sample = _prompt_sample(rng, cfg, lens)
        g = GenerationHyperparameters(n=n, max_new_tokens=8, greedy=True)
        ref, out = _static_and_serving(paged, sample, g)
        _assert_same_output(ref, out)
        st = paged.last_pool_stats
        assert st["pages_recycled"] > 0
        assert st["pool_pages"] == 4
        assert (st["admit_passed_over"] > 0) == (n > 1)

    @pytest.mark.parametrize(
        "lens,n", [((20,), 1), ((20, 12), 4)], ids=["single", "groups"]
    )
    def test_undersized_pool_raises_clear_error(
        self, cfg, params, mesh, rng, lens, n
    ):
        """A pool that cannot hold even one request must fail fast with
        the capacity message, not deadlock the admission loop: a request
        the pool cannot take still ends the round, with or without
        followers queued behind it."""
        paged = _engine(
            cfg, params, mesh, kv_pool_pages=1, max_decode_batch=2
        )
        sample = _prompt_sample(rng, cfg, lens)
        g = GenerationHyperparameters(n=n, max_new_tokens=16, greedy=True)
        with pytest.raises(PagePoolExhausted, match="kv_pool_pages"):
            paged.generate(sample, MicroBatchSpec(), g, inflight=True)


class TestGenServerPageBudget:
    def test_group_splitting_against_budget(self):
        """gen_server splits a batched group so each generate call's
        worst-case token footprint fits the engine's page budget."""
        import threading

        from areal_tpu.system.gen_server import GenerationServer, _Pending

        g = GenerationHyperparameters(n=2, max_new_tokens=10, greedy=True)

        def pend(plen):
            return _Pending(
                qid="q", prompt_ids=list(range(plen)), gconfig=g,
                done=threading.Event(),
            )

        srv = GenerationServer.__new__(GenerationServer)
        calls = []

        class _Eng:
            page_budget_tokens = 100

        srv.engine = _Eng()
        srv._run_subgroup = lambda grp: calls.append(len(grp))
        # footprints: 2*(15+10)=50 each -> two per sub-group.
        srv._run_group([pend(15), pend(15), pend(15), pend(15), pend(15)])
        assert calls == [2, 2, 1]

        # No budget -> one call.
        calls.clear()
        srv.engine = type("E", (), {"page_budget_tokens": None})()
        srv._run_group([pend(15), pend(15), pend(15)])
        assert calls == [3]

    def test_engine_budget_property(self, cfg, params, mesh):
        auto = GeneratorEngine(cfg, params, mesh, eos_token_id=EOS)
        assert auto.page_budget_tokens is None  # auto-sized pool
        capped = GeneratorEngine(
            cfg, params, mesh, eos_token_id=EOS,
            kv_page_size=16, kv_pool_pages=8,
        )
        assert capped.page_budget_tokens == 128


class TestPageSharing:
    """The allocator's copy-on-write sharing + prefix cache contract:
    refcounts track every mapping, shared pages privatise before writes,
    and NOTHING leaks — after every slot releases and the cache clears,
    the whole pool is free and `check()` still holds."""

    def _alloc(self, **kw):
        a = PageAllocator(
            n_pages=kw.pop("n_pages", 8), page_size=kw.pop("page_size", 4),
            n_slots=kw.pop("n_slots", 3), max_pages=kw.pop("max_pages", 4),
        )
        a.debug_check = True  # every mutation re-validates invariants
        return a

    def test_share_diverge_release_leaks_nothing(self):
        a = self._alloc()
        a.reserve(0, 8)  # owner: 2 pages
        owner_pages = [int(p) for p in a.table[0, :2]]
        a.share(1, owner_pages)
        a.share(2, owner_pages)
        assert a.allocated_pages() == 2  # 3 slots, still 2 physical pages
        assert (a.refcount[owner_pages] == 3).all()
        assert a.shared_mappings == 4
        # Follower 1 diverges: privatise its second page before writing.
        pairs = a.ensure_writable(1, 4, 8)
        assert len(pairs) == 1 and pairs[0][0] == owner_pages[1]
        assert a.cow_copies == 1
        assert int(a.table[1, 1]) != owner_pages[1]
        assert int(a.table[1, 0]) == owner_pages[0]  # untouched window
        # Owner's view never moved; refcount dropped by the remap.
        assert [int(p) for p in a.table[0, :2]] == owner_pages
        assert int(a.refcount[owner_pages[1]]) == 2
        for s in (0, 1, 2):
            a.release(s)
        assert a.allocated_pages() == 0
        assert len(a.free) == a.n_pages
        a.check()  # full partition holds: zero leaked pages

    def test_ensure_writable_noop_on_private(self):
        a = self._alloc()
        a.reserve(0, 8)
        assert a.ensure_writable(0, 0, 8) == []
        assert a.cow_copies == 0

    def test_cow_exhaustion_is_clean(self):
        a = self._alloc(n_pages=2)
        a.reserve(0, 8)  # whole pool
        a.share(1, [int(a.table[0, 0])])
        with pytest.raises(PagePoolExhausted, match="privatise"):
            a.ensure_writable(1, 0, 4)
        a.check()  # failed CoW left a consistent state

    def test_prefix_cache_holds_survive_owner_release(self):
        a = self._alloc()
        a.reserve(0, 8)
        pages = [int(p) for p in a.table[0, :2]]
        a.prefix_insert("h", pages)
        a.release(0)  # owner gone; the cache hold keeps the pages live
        assert a.allocated_pages() == 2
        hit = a.prefix_lookup("h")
        assert hit == pages and a.prefix_hits == 1
        a.share(1, hit)
        assert (a.refcount[pages] == 2).all()  # cache hold + slot 1
        a.release(1)
        a.prefix_evict(need_free=a.n_pages)
        assert a.allocated_pages() == 0
        a.check()

    def test_prefix_evict_is_lru(self):
        a = self._alloc(n_pages=4, n_slots=2, max_pages=2)
        a.reserve(0, 8)
        a.prefix_insert("old", [int(a.table[0, 0])])
        a.prefix_insert("new", [int(a.table[0, 1])])
        a.release(0)
        a.prefix_lookup("old")  # refresh: "new" becomes the LRU entry
        a.prefix_evict(need_free=3)
        assert a.prefix_lookup("new") is None
        assert a.prefix_lookup("old") is not None

    def test_invariant_checker_catches_corruption(self):
        from areal_tpu.engines.paging import PagingInvariantError

        a = self._alloc()
        a.reserve(0, 8)
        a.table[0, 0] = a.table[0, 1]  # double-map without refcount
        with pytest.raises(PagingInvariantError):
            a.check()


class TestServingPlaneEquivalence:
    """The serving plane (chunked prefill inside the decode chunk + CoW
    page sharing) must be token-identical to the static decode program —
    while dispatching ZERO standalone prefills and compiling exactly ONE
    program."""

    LENS = (4, 11, 6, 9, 5)

    def _serving(self, cfg, params, mesh, **kw):
        kw.setdefault("prefill_chunk_tokens", 4)
        kw.setdefault("max_decode_batch", 2)
        return _engine(cfg, params, mesh, **kw)

    @pytest.mark.parametrize(
        "lens,n",
        [
            pytest.param(LENS, 1, id="singles"),
            # Groups of 2 on 2 slots: round 1 admits the 17-token owner,
            # passes over its follower and admits the 11-token owner.
            pytest.param((17, 4, 11, 6), 2, id="waiter-passed-over"),
        ],
    )
    def test_token_identical_to_static_program(
        self, cfg, params, mesh, rng, lens, n
    ):
        serving = self._serving(cfg, params, mesh)
        sample = _prompt_sample(rng, cfg, lens)
        g = GenerationHyperparameters(n=n, max_new_tokens=8, greedy=True)
        ref, out = _static_and_serving(serving, sample, g)
        _assert_same_output(ref, out)
        assert serving.prefill_dispatches == 0
        assert serving.decode_compiles == 1
        assert serving.cache_copy_bytes == 0
        assert (serving.last_pool_stats["admit_passed_over"] > 0) == (n > 1)

    @pytest.mark.parametrize(
        "lens",
        [
            pytest.param((17, 9), id="two-groups"),
            # Shareable and one-page prompts interleaved: followers are
            # passed over while requests behind them take the slots.
            pytest.param((21, 6, 13, 4), id="mixed-lengths"),
        ],
    )
    def test_group_sampling_shares_prompt_pages(
        self, cfg, params, mesh, rng, lens
    ):
        """n=4 same-prompt responses: identical tokens to the static
        program, but the prompt's full pages are mapped (not copied)
        into the followers via the prefix cache — visible as shared
        mappings and prefix hits in the pool stats.  A follower whose
        owner is still prefilling is passed over, never duplicated: one
        miss per owner, one hit per follower."""
        serving = self._serving(cfg, params, mesh)
        sample = _prompt_sample(rng, cfg, lens)
        g = GenerationHyperparameters(n=4, max_new_tokens=8, greedy=True)
        ref, out = _static_and_serving(serving, sample, g)
        _assert_same_output(ref, out)
        st = serving.last_pool_stats
        assert st["shared_mappings"] > 0
        assert st["admit_passed_over"] > 0
        owners = sum((l - 1) // 8 > 0 for l in lens)
        assert (st["prefix_misses"], st["prefix_hits"]) == (owners, 3 * owners)
        assert st["cow_copies"] == 0  # steady state: no write ever lands
        # on a shared page, so the CoW safety net stays idle

    def test_share_disabled_still_token_identical(
        self, cfg, params, mesh, rng
    ):
        noshare = self._serving(cfg, params, mesh, kv_share_prefix=False)
        sample = _prompt_sample(rng, cfg, (17, 9))
        g = GenerationHyperparameters(n=4, max_new_tokens=8, greedy=True)
        ref, out = _static_and_serving(noshare, sample, g)
        _assert_same_output(ref, out)
        assert noshare.last_pool_stats["shared_mappings"] == 0

    def _interrupted_then_resumed(self, build, sample, g, at_chunk, parked):
        """generate() interrupted when the `at_chunk`-th chunk is
        dispatched (the loop parks at the next chunk boundary),
        `parked(session)` asserted on the parked state, then resumed
        under UNCHANGED weights."""
        eng = build()
        real_get = eng._get_serving_chunk_fn
        calls = {"n": 0}

        def hooked(*a, **kw):
            fn = real_get(*a, **kw)

            def wrapped(*fa, **fkw):
                calls["n"] += 1
                if calls["n"] == at_chunk:
                    eng.interrupt()
                return fn(*fa, **fkw)

            return wrapped

        eng._get_serving_chunk_fn = hooked
        out = eng.generate(sample, MicroBatchSpec(), g, seed=0)
        assert out is None and eng.interrupted
        parked(eng._session)
        eng.clear_interrupt()
        out = eng.resume_generate()
        assert out is not None and eng.resume_replays == 1
        return eng, out

    def test_resume_on_shared_pages_token_identical(
        self, cfg, params, mesh, rng
    ):
        """Interrupt + resume under UNCHANGED weights while followers
        map the owner's prompt pages: the tail replay clamps to each
        row's private region (never rewriting a shared page), so the
        resumed run reproduces the uninterrupted one token for token."""

        def build():
            # Unreachable EOS keeps rows decoding; max_decode_batch=2
            # forces slot reuse so the interrupt lands with live shares:
            # the two owners fill both slots in round 1 (the followers
            # are passed over), retire after chunk 2, and two followers
            # run chunk 3 on the owner's pages.
            return GeneratorEngine(
                cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
                kv_page_size=8, prefill_chunk_tokens=4,
                max_decode_batch=2,
            )

        sample = _prompt_sample(rng, cfg, (17, 9))
        g = GenerationHyperparameters(n=4, max_new_tokens=24, greedy=True)
        ref = build().generate(sample, MicroBatchSpec(), g, seed=0)

        def parked(st):
            # The interrupt parked mid-flight with at least one follower
            # still mapping shared pages (the scenario under test).
            assert any(
                st.alloc.is_shared(s, 0)
                for s in range(st.n_slots)
                if st.active[s] is not None and int(st.shared_from[s]) > 0
            )

        _, out = self._interrupted_then_resumed(
            build, sample, g, at_chunk=3, parked=parked
        )
        _assert_same_output(ref, out)

    @pytest.mark.parametrize("n", [1, 3], ids=["singles", "waiter-pending"])
    def test_resume_with_row_parked_mid_prefill(
        self, cfg, params, mesh, rng, n
    ):
        """A row whose prompt is still being consumed when the interrupt
        lands has only a prefix of it in cache: the replay re-forwards
        the tail of THAT prefix and the loop goes on consuming the rest,
        token for token as if never interrupted.  In groups of 3 the
        interrupt also finds the owner's followers passed over and still
        queued: resume drops the pre-push owner's claim (its KV is never
        published), so the first of them is admitted as the prompt's
        owner under the current weights and the other shares its pages."""

        def build():
            # chunk_t = max_new = 4 steps of W = 2 lanes: one chunk
            # consumes at most 8 of the 30 prompt tokens.
            return GeneratorEngine(
                cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
                kv_page_size=8, prefill_chunk_tokens=2,
                max_decode_batch=2,
            )

        sample = _prompt_sample(rng, cfg, (30, 5, 21))  # 3n reqs, 2 slots
        g = GenerationHyperparameters(n=n, max_new_tokens=4, greedy=True)
        ref = build().generate(sample, MicroBatchSpec(), g, seed=0)

        def parked(st):
            mid = [
                s for s in range(st.n_slots)
                if st.active[s] is not None and int(st.prefill_rem[s]) > 0
            ]
            assert mid and all(int(st.cache_len[s]) > 0 for s in mid)
            waiting = [
                q for q in st.pending
                if np.asarray(q[2], np.int32).tobytes() in st.inflight_prefix
            ]
            assert len(waiting) == 2 * (n - 1)  # of the 30 and the 21
            assert st.alloc.shared_mappings == 0

        eng, out = self._interrupted_then_resumed(
            build, sample, g, at_chunk=1, parked=parked
        )
        _assert_same_output(ref, out)
        if n > 1:
            # Per prompt over a page: the parked owner and the follower
            # admitted as owner after resume miss, the last follower hits.
            st = eng.last_pool_stats
            assert st["admit_passed_over"] > 0
            assert (st["prefix_misses"], st["prefix_hits"]) == (4, 2)
            assert st["shared_mappings"] > 0 and st["cow_copies"] == 0
        assert {
            sig[0] for sig in eng._gen_fns if isinstance(sig[0], str)
        } == {"serving_chunk", "paged_replay"}

    def test_spec_rides_serving_plane(self, cfg, params, mesh, rng):
        """Speculative decoding is just another ragged q_len in the
        serving chunk: a spec generate dispatches ZERO standalone
        prefills, compiles exactly ONE program across continuous mixed
        admits (5 requests, 2 slots), and its greedy output is token-
        identical to the plain serving path — greedy speculation is the
        argmax chain whatever the draft grouping."""
        spec = self._serving(cfg, params, mesh)
        plain = self._serving(cfg, params, mesh)
        sample = _prompt_sample(rng, cfg, self.LENS)
        gs = GenerationHyperparameters(
            n=1, max_new_tokens=10, greedy=True, spec_decode_k=2
        )
        gp = GenerationHyperparameters(n=1, max_new_tokens=10, greedy=True)
        osp = spec.generate(sample, MicroBatchSpec(), gs)
        opl = plain.generate(sample, MicroBatchSpec(), gp, inflight=True)
        _assert_same_output(osp, opl)
        assert spec.prefill_dispatches == 0
        assert spec.decode_compiles == 1
        assert spec.cache_copy_bytes == 0

    def test_int8_rides_serving_plane(self, cfg, params, mesh, rng):
        """int8 KV rides the same chunked admission and the same spec
        verification: fresh KV is quantized ONCE when first written and
        every later read sees the stored codes, so greedy speculation
        over an int8 pool emits the plain int8 pool's tokens (drafts and
        verification score against the same quantized-cache model)."""
        sample = _prompt_sample(rng, cfg, self.LENS)
        outs = []
        for k in (0, 2):
            eng = self._serving(cfg, params, mesh, kv_cache_dtype="int8")
            g = GenerationHyperparameters(
                n=1, max_new_tokens=8, greedy=True, spec_decode_k=k
            )
            outs.append(
                eng.generate(sample, MicroBatchSpec(), g, inflight=True)
            )
            assert eng.prefill_dispatches == 0
            assert eng.decode_compiles == 1
            assert 0 < eng.last_pool_stats["pool_bytes"]
        _assert_same_tokens(outs[0], outs[1])

    def test_lane_accounting_dead_lanes_zero(self, cfg, params, mesh, rng):
        """The packed stream's lane counters: every dispatched lane is
        either live or budgeted slack (they partition T*steps), and the
        live-but-misassigned count — a packing bug detector — is
        exactly 0.  Dead query lanes are eliminated, not masked."""
        eng = self._serving(cfg, params, mesh)
        sample = _prompt_sample(rng, cfg, self.LENS)
        g = GenerationHyperparameters(
            n=1, max_new_tokens=10, greedy=True, spec_decode_k=2
        )
        eng.generate(sample, MicroBatchSpec(), g)
        assert eng.serving_lane_budget > 0
        assert eng.lanes_dispatched > 0
        assert 0 < eng.lanes_live <= eng.lanes_dispatched
        assert eng.lanes_live + eng.lanes_slack == eng.lanes_dispatched
        assert eng.dead_live_lanes == 0


def _model_rounds(lens, n, n_slots, ps, chunk_t, W, max_new, pass_over):
    """The admission rounds in plain Python, for a lane budget that
    grants every prefilling row its W lanes: a row admitted with `rem`
    prompt tokens to forward prefills for ceil(rem / W) inner steps,
    registers its prompt at the end of that chunk, emits one token a
    step and retires at the end of the chunk in which it emitted
    `max_new`.  `pass_over=False` is the rule this replaced (the first
    waiting follower ends the round).  Returns, per round, the
    (prompt, repeat)s admitted and the live slots, and how often a
    round passed over a request."""
    queue = sorted(
        ((i, r) for i in range(len(lens)) for r in range(n)),
        key=lambda q: -lens[q[0]],
    )
    live = {}  # (prompt, repeat) -> [prefill steps left, budget steps left]
    owners, inflight, cached = set(), set(), set()
    rounds, passed = [], 0
    while queue or live:
        took = []
        for q in list(queue):
            if len(live) == n_slots:
                break
            i = q[0]
            sp = (lens[i] - 1) // ps
            if sp and i in inflight:
                if not pass_over:
                    break
                passed += 1
                continue
            rem = lens[i] - (sp * ps if i in cached else 0)
            if sp and i not in cached:
                inflight.add(i)
                owners.add(q)
            live[q] = [-(-rem // W), -(-rem // W) + max_new]
            queue.remove(q)
            took.append(q)
        rounds.append((took, len(live)))
        for q, left in list(live.items()):
            left[0] -= chunk_t
            left[1] -= chunk_t
            if q in owners and left[0] <= 0 and q[0] in inflight:
                inflight.remove(q[0])
                cached.add(q[0])
            if left[1] <= 0:
                del live[q]
    return rounds, passed


class TestAdmissionPassesOverWaiters:
    """An admission round fills each free slot with the FIRST queued
    request that can be admitted now: a follower whose owner is still
    prefilling keeps its place and is passed over, the requests behind
    it stop waiting with it.  The engine's rounds are held to the plain
    model above, request by request."""

    CASES = {
        # lens, page size, slots, W, max_new
        # Every prompt fits one page: nothing is shareable, nothing
        # waits, and the schedule is the replaced rule's.
        "none-shareable": ((7, 5, 6, 4, 3, 8), 8, 8, 2, 4),
        # Every prompt is over a page: round 1 admits the four owners
        # (the replaced rule admitted one) and passes over all twelve
        # followers; the 30-token prompt prefills for four chunks.
        "all-shareable": ((30, 21, 17, 12), 8, 8, 2, 4),
        # q1p5b-serving-waves scaled down: 24 requests over 16 slots,
        # two of six prompts over a page, the queue's head a waiting
        # follower, retirement two chunks after the first.
        "cell-mix": ((27, 10, 8, 6, 5, 4), 8, 16, 4, 64),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_rounds_match_the_plain_model(self, cfg, params, mesh, rng, case):
        lens, ps, n_slots, W, max_new = self.CASES[case]
        n = 4
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
            kv_page_size=ps, prefill_chunk_tokens=W,
            max_decode_batch=n_slots,
            # Every prefilling row gets its W lanes (the model's premise).
            serving_admit_lanes=n_slots * W,
        )
        sample = _prompt_sample(rng, cfg, lens)
        g = GenerationHyperparameters(
            n=n, max_new_tokens=max_new, greedy=True
        )
        chunk_t = min(32, max_new)
        rounds = []
        real = eng._take_admits_serving

        def recorded(st):
            before = {a for a in st.active if a is not None}
            admitted = real(st)
            now = [a for a in st.active if a is not None]
            rounds.append(
                (sorted(a for a in now if a not in before), len(now))
            )
            return admitted

        eng._take_admits_serving = recorded
        ref, out = _static_and_serving(eng, sample, g)
        _assert_same_output(ref, out)

        want, passed = _model_rounds(
            lens, n, n_slots, ps, chunk_t, W, max_new, pass_over=True
        )
        old, _ = _model_rounds(
            lens, n, n_slots, ps, chunk_t, W, max_new, pass_over=False
        )
        assert [(sorted(t), l) for t, l in want] == rounds
        st = eng.last_pool_stats
        assert st["chunks"] == len(want)
        assert st["admit_passed_over"] == passed
        # One miss per owner — a passed-over look costs none — one hit
        # per follower, no page duplicated or copied.
        shareable = sum((l - 1) // ps > 0 for l in lens)
        assert st["prefix_misses"] == shareable
        assert st["prefix_hits"] == shareable * (n - 1)
        assert st["cow_copies"] == 0
        assert st["peak_live_slots"] == max(l for _, l in want)
        if case == "none-shareable":
            assert st["admit_passed_over"] == 0
            assert want == old
            return
        assert st["admit_passed_over"] > 0
        assert len(want) < len(old)
        assert old[0][1] == 1  # the replaced rule: the owner, alone
        assert rounds[0][1] == min(
            n_slots, len(lens) * n - shareable * (n - 1)
        )
        if case == "cell-mix":
            assert rounds[0][1] == n_slots  # every slot, in round 1
        # No follower joins before its owner registered, and the queue's
        # head — the longest prompt's first follower, passed over in
        # round 1 — joins in the first round after that with a free slot.
        joined = {q: k for k, (took, _) in enumerate(rounds) for q in took}
        registered = {  # the owner's round + its chunks of prefill
            i: joined[(i, 0)] + -(-l // (W * chunk_t))
            for i, l in enumerate(lens) if (l - 1) // ps
        }
        for i, k in registered.items():
            assert all(joined[(i, r)] >= k for r in range(1, n))
        head = max(registered, key=lambda i: lens[i])
        assert joined[(head, 1)] == next(
            k for k in range(registered[head], len(rounds)) if rounds[k][0]
        )


class TestTwoProgramsOnly:
    """What went with the dense and two-program inflight paths stays
    gone: the options that selected them are rejected, the variables
    that shadowed the constructor are inert, and an engine only ever
    builds the static program and the serving plane's three."""

    SERVING_PROGRAMS = {"serving_chunk", "copy_pages", "paged_replay"}

    def test_prefill_chunk_tokens_zero_is_rejected(self, cfg, params, mesh):
        with pytest.raises(ValueError, match="two-program admit path"):
            GeneratorEngine(
                cfg, params, mesh, eos_token_id=EOS, prefill_chunk_tokens=0
            )

    def test_kv_paged_is_no_longer_an_option(self, cfg, params, mesh):
        with pytest.raises(TypeError, match="kv_paged"):
            GeneratorEngine(
                cfg, params, mesh, eos_token_id=EOS, kv_paged=False
            )

    def test_removed_environment_shadows_are_inert(
        self, cfg, params, mesh, monkeypatch
    ):
        monkeypatch.setenv("AREAL_PAGED_KV", "0")
        monkeypatch.setenv("AREAL_PREFILL_CHUNK_TOKENS", "3")
        monkeypatch.setenv("AREAL_KV_SHARE_PREFIX", "0")
        monkeypatch.setenv("AREAL_SERVING_ADMIT_LANES", "5")
        eng = GeneratorEngine(cfg, params, mesh, eos_token_id=EOS)
        assert eng.prefill_chunk_tokens == 8
        assert eng.kv_share_prefix is True
        assert eng.serving_admit_lanes == 0
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=EOS, prefill_chunk_tokens=4,
            kv_share_prefix=False, serving_admit_lanes=2,
        )
        assert eng.prefill_chunk_tokens == 4
        assert eng.kv_share_prefix is False
        assert eng.serving_admit_lanes == 2

    def test_engine_builds_only_static_and_serving_programs(
        self, cfg, params, mesh, rng
    ):
        eng = _engine(cfg, params, mesh, max_decode_batch=2)
        sample = _prompt_sample(rng, cfg, (4, 11, 6))
        g = GenerationHyperparameters(n=2, max_new_tokens=6, greedy=True)
        eng.generate(sample, MicroBatchSpec(), g, inflight=False)
        n_static = len(eng._gen_fns)
        assert n_static and all(
            not isinstance(sig[0], str) for sig in eng._gen_fns
        )
        eng.generate(sample, MicroBatchSpec(), g, inflight=True)
        gs = GenerationHyperparameters(
            n=2, max_new_tokens=6, greedy=True, spec_decode_k=2
        )
        eng.generate(sample, MicroBatchSpec(), gs)
        named = [sig[0] for sig in eng._gen_fns if isinstance(sig[0], str)]
        assert len(named) == len(eng._gen_fns) - n_static
        assert set(named) <= self.SERVING_PROGRAMS
        assert named.count("serving_chunk") == 2  # K=0 and K=2


class TestRaggedPagedKernel:
    """The paged attention kernel (`ragged_paged_attention_kernel`,
    interpreted here): decode, chunked-prefill and spec-verify lanes
    mixed in one stream, read in place from a STACKED pool at a nonzero
    layer index, must match the XLA gather form, emit exact zeros for
    dead lanes (valid_to == 0: no item of the work list touches them and
    the unconditional finish normalises the empty accumulator), and obey
    the sentinel page rule under poisoning."""

    N_LAYERS, LAYER, PS, MP = 3, 2, 8, 3

    def _stream(self, rng, n_q=6, n_kv=2, d=16, dtype=jnp.float32):
        ps, mp = self.PS, self.MP
        # Windows at the page edges, dead lanes in between, then the 4
        # lanes of ONE prefilling row (one table row, windows 1 apart)
        # and the 3 of a spec-verify row: T = 16, two tiles of 8 lanes
        # at rep 6, the prefilling row across their boundary.
        windows = [1, ps - 1, ps, ps + 1, mp * ps, 0, 0]
        shared = [(10, 11, 12, 13), (3, 4, 5)]
        rows = [[w] for w in windows] + [list(g) for g in shared] + [[0], [20]]
        n_pool = sum(-(-max(r) // ps) for r in rows) + 2
        perm = rng.permutation(n_pool - 1)  # the last page stays unmapped
        pt_tok, vt, nxt = [], [], 0
        for r in rows:
            n = -(-max(r) // ps)
            row = np.full((mp,), n_pool, np.int32)  # sentinel past the window
            row[:n] = perm[nxt:nxt + n]
            nxt += n
            pt_tok += [row] * len(r)
            vt += r
        shape = (self.N_LAYERS, n_pool, ps, n_kv * d)
        k = jnp.asarray(rng.standard_normal(shape), dtype)
        v = jnp.asarray(rng.standard_normal(shape), dtype)
        q = jnp.asarray(rng.standard_normal((len(vt), n_q, d)), dtype)
        return (
            q, k, v, jnp.int32(self.LAYER), jnp.asarray(np.stack(pt_tok)),
            jnp.asarray(np.array(vt, np.int32)),
        )

    @pytest.mark.parametrize(
        "n_q,n_kv,d", [(12, 2, 128), (16, 16, 128), (28, 4, 128), (6, 2, 16)],
        ids=["q1p5b_12x2", "olmoe_16x16", "q7b_28x4", "toy_6x2"],
    )
    def test_kernel_matches_xla_form_and_kills_dead_lanes(
        self, rng, n_q, n_kv, d
    ):
        from areal_tpu.ops.attention import ragged_paged_attention

        args = self._stream(rng, n_q, n_kv, d)
        out_x = ragged_paged_attention(*args, use_kernel=False)
        out_k = ragged_paged_attention(*args, use_kernel=True)
        np.testing.assert_allclose(
            np.asarray(out_x), np.asarray(out_k), rtol=2e-5, atol=2e-5
        )
        # Dead lanes (valid_to == 0): exact zeros from BOTH forms.
        dead = np.asarray(args[-1]) == 0
        assert dead.sum() == 3
        assert float(jnp.max(jnp.abs(out_x[dead]))) == 0.0
        assert float(jnp.max(jnp.abs(out_k[dead]))) == 0.0

    def test_reads_the_layer_it_is_given(self, rng):
        """Another layer's pages are another answer; a layer's own pages
        alone decide it."""
        from areal_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention_kernel,
        )

        q, k, v, li, pt_tok, vt = self._stream(rng)
        out = ragged_paged_attention_kernel(q, k, v, li, pt_tok, vt)
        others = jnp.arange(self.N_LAYERS) != self.LAYER
        k_bad = jnp.where(others[:, None, None, None], 1e9, k)
        out_bad = ragged_paged_attention_kernel(q, k_bad, v, li, pt_tok, vt)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_bad))
        out_0 = ragged_paged_attention_kernel(q, k, v, jnp.int32(0), pt_tok, vt)
        assert float(jnp.max(jnp.abs(out - out_0))) > 1e-2

    def test_sentinel_pages_add_no_mass(self, rng):
        from areal_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention_kernel,
        )

        q, k, v, li, pt_tok, vt = self._stream(rng)
        n_pool = k.shape[1]
        assert int(jnp.max(pt_tok)) == n_pool  # sentinel entries exist
        k_bad = k.at[:, n_pool - 1].set(1e9)
        v_bad = v.at[:, n_pool - 1].set(1e9)
        out = ragged_paged_attention_kernel(q, k, v, li, pt_tok, vt)
        out_bad = ragged_paged_attention_kernel(
            q, k_bad, v_bad, li, pt_tok, vt
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_bad))

    def test_int8_pool_parity(self, rng):
        from areal_tpu.ops.attention import ragged_paged_attention

        q, k, _, li, pt_tok, vt = self._stream(rng)
        r = np.random.default_rng(3)
        k8 = jnp.asarray(r.integers(-127, 128, k.shape), jnp.int8)
        v8 = jnp.asarray(r.integers(-127, 128, k.shape), jnp.int8)
        s_shape = (*k.shape[:2], 2, self.PS)  # head-major [L, P, n_kv, ps]
        ks = jnp.asarray(
            np.abs(r.standard_normal(s_shape)) + 0.1, jnp.bfloat16
        )
        vs = jnp.asarray(
            np.abs(r.standard_normal(s_shape)) + 0.1, jnp.bfloat16
        )
        o_x, o_k = (
            ragged_paged_attention(
                q, k8, v8, li, pt_tok, vt, ks, vs, use_kernel=use
            )
            for use in (False, True)
        )
        # The kernel scales the fp32 scores and probabilities where the
        # XLA form scales the codes: the same numbers, rounded elsewhere.
        np.testing.assert_allclose(
            np.asarray(o_x), np.asarray(o_k), rtol=1e-4, atol=1e-4
        )

    def test_schedule_lists_live_pages_once_a_run(self, rng):
        """The work list: one item per (run of lanes sharing a page,
        page), nothing for dead lanes or pages past a window."""
        from areal_tpu.ops.pallas.paged_attention import (
            lane_tile, live_page_schedule,
        )

        _, k, _, _, pt_tok, vt = self._stream(rng)
        ps, rep = self.PS, 6
        assert lane_tile(3) == 16 and lane_tile(1) == 16 and lane_tile(7) == 16
        sch = live_page_schedule(pt_tok, vt, k.shape[1], ps, rep)
        tl = lane_tile(rep)
        assert tl == 8 and sch.valid_rows.shape == (2, tl * rep, 1)
        lo = np.asarray(sch.tile_lo)
        n_work = int(lo[-1])
        # Lanes 7..10 are one row of 2 pages, cut by the tile boundary
        # into runs of 1 and 3 lanes a page: 4 items for 8 (lane, page)
        # pairs; lanes 11..13 another of 1 page: 1 item for 3.
        pages_live = int(np.sum(-(-np.asarray(vt) // ps)))
        assert n_work == pages_live - 4 - 2
        meta = np.asarray(sch.meta)[:n_work]
        col, first, n = meta >> 16, (meta >> 8) & 0xFF, meta & 0xFF
        assert n.sum() == pages_live and n.max() == 3
        assert ((first + n) <= tl).all() and (col < self.MP).all()
        pages = np.asarray(sch.page)[:n_work]
        lanes = np.arange(len(vt))
        for w in range(n_work):
            tile = np.searchsorted(lo, w, side="right") - 1
            run = lanes[tile * tl + first[w]: tile * tl + first[w] + n[w]]
            assert (np.asarray(pt_tok)[run, col[w]] == pages[w]).all()
            assert (np.asarray(vt)[run] > col[w] * ps).all()

    def test_a_mesh_of_many_devices_keeps_the_xla_form(self, cfg, params):
        """The kernel is one device's program: where the generator's mesh
        spreads the lanes or the pool's heads, it asks for the XLA form
        whatever the platform."""
        for layout, want in (("d1", None), ("d2", False), ("m2", False)):
            pc = ParallelConfig.from_str(layout)
            eng = _engine(
                cfg, params, make_mesh(pc, jax.devices()[: pc.world_size])
            )
            assert eng._paged_kernel is want, layout

    def test_generate_kernel_token_for_token(
        self, rng, cfg, params, mesh, monkeypatch
    ):
        """One `generate()` on the serving plane with the kernel
        (interpreted) against the XLA form, token for token in fp32:
        waves of admission, chunked prefill lanes beside decode lanes."""
        sample = _prompt_sample(rng, cfg, [5, 19, 11, 3, 26, 9])
        g = GenerationHyperparameters(n=1, max_new_tokens=12, greedy=True)
        outs = []
        for use in (False, True):
            monkeypatch.setattr(
                GeneratorEngine, "_paged_kernel", property(lambda self: use)
            )
            eng = _engine(
                cfg, params, mesh, max_decode_batch=4,
                prefill_chunk_tokens=4,
            )
            outs.append(
                eng.generate(sample, MicroBatchSpec(), g, inflight=True)
            )
            assert eng.lanes_live > 0
            assert 0 < eng.pages_live <= eng.pages_addressed
            assert eng.last_pool_stats["pages_live"] == eng.pages_live
        _assert_same_output(*outs)


class TestGenServerBudgetValidation:
    """The splitter's capacity check covers EVERY request — singletons
    included (they previously bypassed it entirely) — and uses the
    engine's CoW-aware footprint when available."""

    def _srv(self, engine):
        import threading  # noqa: F401

        from areal_tpu.system.gen_server import GenerationServer

        srv = GenerationServer.__new__(GenerationServer)
        srv.engine = engine
        return srv

    def _pend(self, plen, n=1, max_new=10):
        import threading

        from areal_tpu.system.gen_server import _Pending

        g = GenerationHyperparameters(
            n=n, max_new_tokens=max_new, greedy=True
        )
        return _Pending(
            qid="q", prompt_ids=list(range(plen)), gconfig=g,
            done=threading.Event(),
        )

    def test_oversized_singleton_fails_cleanly(self):
        class _Eng:
            page_budget_tokens = 100

        srv = self._srv(_Eng())
        calls = []
        srv._run_subgroup = lambda grp: calls.append(len(grp))
        big = self._pend(200)  # 210 tokens > 100 even alone
        ok = self._pend(15)  # 25 tokens
        srv._run_group([big, ok])
        assert calls == [1]  # only the feasible request ran
        assert big.done.is_set()
        assert big.error and "exceeds the KV page budget" in big.error
        assert ok.error is None

    def test_split_uses_cow_aware_footprint(self, cfg, params, mesh):
        """A real serving engine: a 4-response group over a 60-token
        prompt costs 56 (shared prompt pages) + 4*(tail + max_new), not
        4*(60 + max_new) — so a budget that the dense formula would
        split (or reject) admits the group WHOLE."""
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=EOS,
            kv_page_size=8, kv_pool_pages=20,  # budget: 160 tokens
        )
        # sp = (60-1)//8 = 7 full pages -> 56 + 4*(4 + 10) = 112 <= 160;
        # the dense product 4*70 = 280 would have rejected it outright.
        assert eng.group_footprint_tokens(60, 10, 4) == 112
        srv = self._srv(eng)
        calls = []
        srv._run_subgroup = lambda grp: calls.append(len(grp))
        p = self._pend(60, n=4)
        srv._run_group([p])
        assert calls == [1] and p.error is None
        # Sharing off -> dense product -> rejected up front.
        eng.kv_share_prefix = False
        assert eng.group_footprint_tokens(60, 10, 4) == 280
        p2 = self._pend(60, n=4)
        srv._run_group([p2])
        assert calls == [1]  # no new call
        assert p2.error and "exceeds the KV page budget" in p2.error
