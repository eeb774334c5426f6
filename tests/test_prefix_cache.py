"""Radix-tree prefix cache tests (serving/prefix_cache.py).

Load-bearing properties, in order of importance:

1. **Bitwise neutrality** (the repo's signature invariant, extended):
   a cache-hit request — seated with its prefix pages aliased from the
   trie and only the tail prefilled — produces a token stream BITWISE
   identical to the same request served cold, greedy AND sampled,
   speculation on AND off. Reuse changes which pages a block table
   points at, never a gathered value or a sampled token.
2. **Exactly-once page release** (the shared-free bugfix satellite):
   a page aliased by the trie and N sequences holds N+1 references and
   returns to the free list exactly once — each holder's ``free``
   drops ITS reference, each seat's ``uncommit`` returns only what IT
   committed (a hit commits only the non-resident tail), and
   ``check_balanced`` audits the trie-held steady state.
3. **Eviction safety**: LRU reclaims only unreferenced leaves (never a
   page a live sequence aliases, never the chain a candidate is about
   to hit), under both the ``prefix_cache_pages`` cap and pool
   commitment pressure — and the pool drains balanced after the churn.
4. **Preempt-and-restore** (ROADMAP item 4 follow-on): a preempted
   victim's pages enter the trie at eviction, its re-seat hits them,
   and ``preempted_token_recompute`` drops to the divergent tail —
   while the output stays bitwise the uninterrupted run's.
5. **Swap flush**: KV cached under old weights never seeds a
   new-epoch request; old-epoch in-flight sequences free cleanly and
   never re-index their pages.

Engines compile real XLA programs, so the model is tiny and the
bitwise matrix covers every axis value (greedy/sampled × spec 0/2)
without the full product.
"""

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.serving import (
    Engine,
    PagePool,
    PrefixCache,
)

VOCAB = 31
MAX_LEN = 64
PS = 4  # kv page size under test: small, so short prompts span pages


@pytest.fixture(scope="module")
def lm():
    model = get_model(
        "transformer_lm", num_classes=VOCAB, num_layers=1, num_heads=2,
        hidden_dim=16, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


def make_engine(lm, **kw):
    model, params = lm
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("kv_page_size", PS)
    kw.setdefault("prefill_chunk", 4)
    return Engine(model, params, ServeConfig(**kw))


PREAMBLE = (np.arange(1, 21, dtype=np.int32) * 3) % VOCAB  # 20 tokens


def _serve(eng, prompts, **submit_kw):
    """Submit ``prompts`` one at a time, each run to completion —
    uids follow submission order, so outputs are comparable across
    engines (fold_in(seed, uid) parity)."""
    out = []
    for p in prompts:
        eng.submit(p, **submit_kw)
        out.extend(eng.run())
    return {f.uid: f for f in out}


# -- pool refcounts (the shared-free / double-uncommit audit) ---------------
class TestSharedPages:
    def test_shared_page_freed_exactly_once(self):
        """Two holders (trie + a sequence) → two frees to release; the
        page hits the free list exactly once, and a third free raises
        like any double free."""
        pool = PagePool(num_pages=4, page_size=PS)
        (p,) = pool.alloc(1, committed=False)
        pool.incref([p])
        assert pool.refcount(p) == 2
        pool.free([p])                     # sequence finishes
        assert pool.refcount(p) == 1
        assert pool.num_free == 3          # still held by the trie
        pool.free([p])                     # trie evicts
        assert pool.refcount(p) == 0
        assert pool.num_free == 4
        with pytest.raises(ValueError, match="double free|not allocated"):
            pool.free([p])

    def test_uncommit_released_exactly_once_per_committer(self):
        """The double-uncommit audit: a hit request commits only its
        tail, so two sequences sharing a page each release exactly
        their OWN commitment — total commitment conserves."""
        pool = PagePool(num_pages=8, page_size=PS)
        pool.commit(3)                     # cold request: 3-page worst
        pages = pool.alloc(2)              # wrote 2, 1 commitment unused
        pool.incref([pages[0]])            # trie indexes page 0
        pool.free(pages, uncommit=1)       # cold finish: its own refund
        assert pool.committed == 0
        pool.commit(2)                     # hit request: tail-only commit
        pool.incref([pages[0]])            # ...aliases the cached page
        tail = pool.alloc(1)
        pool.free([pages[0]] + tail, uncommit=1)
        assert pool.committed == 0         # never released twice
        pool.free([pages[0]])              # trie lets go last
        pool.check_balanced()

    def test_incref_free_page_raises(self):
        pool = PagePool(num_pages=2, page_size=PS)
        with pytest.raises(ValueError, match="not allocated"):
            pool.incref([1])

    def test_check_balanced_audits_trie_pages(self):
        pool = PagePool(num_pages=4, page_size=PS)
        pages = pool.alloc(2, committed=False)
        pool.check_balanced(cached=set(pages))  # trie holds both: OK
        with pytest.raises(AssertionError, match="drift"):
            pool.check_balanced(cached={pages[0]})
        pool.incref([pages[0]])
        with pytest.raises(AssertionError, match="stranded"):
            pool.check_balanced(cached=set(pages))


# -- trie mechanics ---------------------------------------------------------
class TestTrie:
    def _pool_cache(self, max_pages=None):
        pool = PagePool(num_pages=16, page_size=PS)
        return pool, PrefixCache(PS, max_pages=max_pages)

    def test_page_granular_match_and_cap(self):
        pool, cache = self._pool_cache()
        toks = np.arange(10, dtype=np.int32)     # 2 full pages + 2 tail
        pages = pool.alloc(3, committed=False)
        adopted, _ = cache.insert_chain(toks, pages, pool)
        assert adopted == set(pages[:2])          # partial page never indexed
        pool.free([pages[2]])
        # Full-prefix probe: both pages; the fresh-request cap
        # (prompt - 1) keeps the last position un-aliased when the
        # prompt is exactly the cached chain.
        assert cache.probe(toks, max_tokens=10) == pages[:2]
        assert cache.probe(toks[:8], max_tokens=7) == pages[:1]
        assert cache.probe(toks[:3], max_tokens=3) == []
        # Divergent second page: only the shared first page matches.
        other = np.concatenate([toks[:4], toks[:4]])
        assert cache.probe(other, max_tokens=8) == pages[:1]

    def test_duplicate_insert_keeps_resident_page(self):
        pool, cache = self._pool_cache()
        toks = np.arange(8, dtype=np.int32)
        first = pool.alloc(2, committed=False)
        assert cache.insert_chain(toks, first, pool)[0] == set(first)
        dup = pool.alloc(2, committed=False)
        adopted, _ = cache.insert_chain(toks, dup, pool)
        assert adopted == set()                   # trie keeps the original
        pool.free(dup)
        assert cache.pages_held() == set(first)

    def test_lru_eviction_order_refs_and_pinning(self):
        pool, cache = self._pool_cache()
        chains = []
        for i in range(3):
            toks = (np.arange(8, dtype=np.int32) + 11 * i) % VOCAB
            pages = pool.alloc(2, committed=False)
            cache.insert_chain(toks, pages, pool)
            chains.append((toks, pages))
        # Touch chain 0 (recency) and alias chain 1 (a live reference).
        held = cache.claim(chains[0][0], pool, max_tokens=8)
        assert held == chains[0][1]
        seq_ref = cache.claim(chains[1][0], pool, max_tokens=8)
        # Pressure: need every free page back. Evictable = chain 2 only
        # (chain 0 pinned by the caller, chain 1 referenced).
        evicted = cache.evict_until(pool, 16, pinned=set(chains[0][1]))
        assert evicted == 2
        assert cache.pages_held() == set(chains[0][1] + chains[1][1])
        pool.free(held)
        pool.free(seq_ref)
        evicted = cache.evict_until(pool, 16)
        assert evicted == 4 and cache.num_pages == 0
        pool.check_balanced()

    def test_max_pages_cap_evicts_lru(self):
        pool, cache = self._pool_cache(max_pages=2)
        a = np.arange(8, dtype=np.int32)
        b = (np.arange(8, dtype=np.int32) + 13) % VOCAB
        pa = pool.alloc(2, committed=False)
        cache.insert_chain(a, pa, pool)
        pb = pool.alloc(2, committed=False)
        adopted, evicted = cache.insert_chain(b, pb, pool)
        assert adopted == set(pb) and evicted == 2  # a's chain aged out
        assert cache.num_pages == 2
        assert cache.probe(a, max_tokens=8) == []
        assert cache.probe(b, max_tokens=8) == pb

    def test_flush_respects_live_references(self):
        pool, cache = self._pool_cache()
        toks = np.arange(8, dtype=np.int32)
        pages = pool.alloc(2, committed=False)
        cache.insert_chain(toks, pages, pool)
        aliased = cache.claim(toks, pool, max_tokens=8)
        assert cache.flush(pool) == 2
        assert cache.num_pages == 0
        # The in-flight sequence still owns its aliased pages.
        assert pool.refcount(aliased[0]) == 1
        pool.free(aliased)
        pool.check_balanced()


# -- engine integration: the bitwise pin ------------------------------------
# Every axis value (greedy/sampled, spec 0/2) without the full product.
BITWISE_CASES = [(0.0, 0), (0.8, 0), (0.0, 2), (0.8, 2)]


class TestCacheHitBitwise:
    @pytest.mark.parametrize("temp,spec_k", BITWISE_CASES)
    def test_hit_bitwise_equals_cold(self, lm, temp, spec_k):
        """THE invariant: request B shares A's preamble; on the warm
        engine B seats with the preamble aliased from the trie and
        prefills only its tail — its tokens must equal the cold
        engine's bitwise, for every sampling/speculation mode."""
        prompts = [np.concatenate([PREAMBLE, np.asarray(s, np.int32)])
                   for s in ([3, 5], [7, 9, 11])]
        cold = make_engine(lm, temperature=temp, spec_k=spec_k)
        warm = make_engine(lm, temperature=temp, spec_k=spec_k,
                           prefix_cache=True)
        cold_out = _serve(cold, prompts)
        warm_out = _serve(warm, prompts)
        sw = warm.stats()
        assert sw["prefix_cache_hit_requests"] == 1
        # B's hit covers the preamble's full pages (20 tokens = 5 pages).
        assert sw["prefix_cache_hit_tokens"] == 20
        assert sw["ledger_tokens_prefix_hit"] == 20
        assert cold.stats()["prefix_cache_hit_tokens"] == 0
        for uid, fin in cold_out.items():
            assert np.array_equal(fin.tokens, warm_out[uid].tokens), uid
            assert fin.finish_reason == warm_out[uid].finish_reason
        # Reused positions bill to prefix_hit, never to prefill: the
        # two engines' prefill+hit totals cover the same positions.
        sc = cold.stats()
        assert (sw["ledger_tokens_prefill"] + sw["prefix_cache_hit_tokens"]
                == sc["ledger_tokens_prefill"])
        warm.check_balanced()
        cold.check_balanced()

    def test_identical_prompt_keeps_one_position_cold(self, lm):
        """A prompt ENTIRELY resident still prefills its last position:
        the first token samples from computed logits, never from
        memory. The hit caps at floor((prompt-1)/page)*page."""
        eng = make_engine(lm, prefix_cache=True)
        # 20-token prompt: cap 19 -> 4 full pages = 16 aliased tokens.
        out = _serve(eng, [PREAMBLE, PREAMBLE])
        cold = make_engine(lm)
        ref = _serve(cold, [PREAMBLE, PREAMBLE])
        st = eng.stats()
        assert st["prefix_cache_hit_tokens"] == 16
        for uid in ref:
            assert np.array_equal(ref[uid].tokens, out[uid].tokens)
        eng.check_balanced()

    def test_stats_keys_present_when_off(self, lm):
        eng = make_engine(lm)
        st = eng.stats()
        for key in ("prefix_cache_hit_tokens", "prefix_cache_hit_requests",
                    "prefix_cache_inserted_pages",
                    "prefix_cache_evicted_pages",
                    "prefix_cache_pages_held", "ledger_tokens_prefix_hit"):
            assert st[key] == 0


class TestEvictionPressure:
    def test_pool_pressure_evicts_and_stays_balanced(self, lm):
        """Distinct prompts fill the trie until admission cannot commit
        a worst case; the LRU pressure path reclaims unreferenced trie
        pages, every request still completes, and the drained pool is
        balanced with the survivors accounted to the trie."""
        # Pool = 2 slots' worst case exactly: any trie residue blocks
        # the next admission, so eviction MUST run for later seats.
        eng = make_engine(lm, prefix_cache=True, max_len=32,
                          kv_pages=16, max_new_tokens=4)
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, VOCAB, size=12).astype(np.int32)
                   for _ in range(6)]
        out = _serve(eng, prompts)
        assert len(out) == 6
        st = eng.stats()
        assert st["prefix_cache_inserted_pages"] > 0
        assert st["prefix_cache_evicted_pages"] > 0
        eng.check_balanced()

    def test_cap_pressure_stays_balanced(self, lm):
        eng = make_engine(lm, prefix_cache=True, prefix_cache_pages=3,
                          max_new_tokens=4)
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, VOCAB, size=10).astype(np.int32)
                   for _ in range(4)]
        _serve(eng, prompts)
        st = eng.stats()
        assert st["prefix_cache_pages_held"] <= 3
        assert st["prefix_cache_evicted_pages"] > 0
        eng.check_balanced()


class TestPreemptAndRestore:
    @pytest.mark.parametrize("temp", [0.0, 0.8])
    def test_victim_reseat_hits_own_pages(self, lm, temp):
        """ROADMAP item 4 follow-on: the victim's committed pages enter
        the trie at eviction, so its re-seat aliases them back and
        preempted_token_recompute drops to the divergent tail — with
        the output still bitwise the no-preemption oracle's."""

        def run(prefix_cache):
            eng = make_engine(lm, max_batch=1, num_tiers=2,
                              temperature=temp, max_new_tokens=8,
                              prefix_cache=prefix_cache)
            low = eng.submit(PREAMBLE, priority=1, max_new_tokens=8)
            # finish prefill, emit a few tokens: seven calls deliver seven
            # steps and leave the eighth in flight, which lands before
            # the preemption — the victim leaves where eight in-order
            # calls left it
            for _ in range(7):
                eng.step()
            assert len(eng.scheduler.sequence(0).tokens) >= 1
            high = eng.submit(np.asarray([2, 4, 6], np.int32),
                              priority=0, max_new_tokens=4)
            done = {f.uid: f for f in eng.run()}
            eng.check_balanced()
            return eng, done, low, high

        e_off, d_off, lo_off, _ = run(False)
        e_on, d_on, lo_on, _ = run(True)
        assert np.array_equal(d_off[lo_off.uid].tokens,
                              d_on[lo_on.uid].tokens)
        s_off, s_on = e_off.stats(), e_on.stats()
        assert s_off["requests_preempted"] == s_on["requests_preempted"] >= 1
        # Cache off: the whole carried prefix recomputes. Cache on: the
        # re-seat hits the victim's own pages — only the page-unaligned
        # tail (and positions written after the eviction snapshot)
        # recompute.
        assert s_on["requests_preempted"] >= 1
        assert 0 < s_on["preempted_token_recompute"] \
            < s_off["preempted_token_recompute"]
        assert s_on["prefix_cache_hit_tokens"] > 0


class TestSwapFlush:
    def test_barrier_flushes_and_old_epoch_never_reindexes(self, lm):
        model, params = lm
        params2 = model.init(jax.random.PRNGKey(9),
                             np.zeros((1, 8), np.int32))["params"]
        eng = make_engine(lm, prefix_cache=True)
        _serve(eng, [np.concatenate([PREAMBLE, np.asarray([3], np.int32)])])
        assert eng.prefix_cache.num_pages > 0
        # In-flight across the barrier: seat a second preamble request,
        # let it hit, then swap mid-sequence.
        eng.submit(np.concatenate([PREAMBLE, np.asarray([8], np.int32)]))
        eng.step()
        assert eng.stats()["prefix_cache_hit_tokens"] == 20
        eng.arm_swap(params2, epoch=1)
        eng.step()  # lands the step in flight: the barrier waits
        eng.step()  # barrier: trie flushed, epoch bumped
        assert eng.prefix_cache.num_pages == 0
        fins = eng.run()  # old-epoch sequence finishes under new weights
        assert fins
        # ...and did NOT re-index its stale-weight pages.
        assert eng.prefix_cache.num_pages == 0
        # A post-swap twin is COLD (no stale-KV hit), then repopulates.
        _serve(eng, [np.concatenate([PREAMBLE, np.asarray([5], np.int32)])])
        assert eng.stats()["prefix_cache_hit_tokens"] == 20  # unchanged
        assert eng.prefix_cache.num_pages > 0
        eng.check_balanced()


class TestScenario:
    def test_shared_prefix_deterministic_and_admissible(self):
        from tools.traffic import make_scenario

        kw = dict(seed=5, requests=40, rate=100.0, mean_prompt_len=16,
                  max_prompt_len=24, max_new_tokens=8, vocab_size=VOCAB,
                  budget=32)
        a = make_scenario("shared_prefix", **kw)
        b = make_scenario("shared_prefix", **kw)
        assert len(a) == 40
        for ra, rb in zip(a, b):
            assert ra.arrival_s == rb.arrival_s
            assert np.array_equal(ra.prompt, rb.prompt)
            assert ra.max_new_tokens == rb.max_new_tokens
            assert ra.tenant == rb.tenant
        for r in a:
            assert 1 <= r.prompt.size <= 24
            assert r.prompt.size + r.max_new_tokens <= 32
        # The point of the scenario: prompts actually share preambles.
        heads = {}
        for r in a:
            key = r.prompt[:8].tobytes()
            heads[key] = heads.get(key, 0) + 1
        assert max(heads.values()) >= 5, heads.values()
        c = make_scenario("shared_prefix", **{**kw, "seed": 6})
        assert any(not np.array_equal(ra.prompt, rc.prompt)
                   for ra, rc in zip(a, c))


class TestJournalColdStart:
    def test_recovery_cold_starts_trie(self, lm, tmp_path):
        """The trie is not journaled: a restart replays bitwise with an
        empty cache and repopulates as recovered work completes."""
        prompts = [np.concatenate([PREAMBLE, np.asarray(s, np.int32)])
                   for s in ([3], [9])]
        eng1 = make_engine(lm, prefix_cache=True,
                           journal_dir=str(tmp_path))
        eng1.recover()
        out1 = _serve(eng1, prompts)
        assert eng1.stats()["prefix_cache_hit_tokens"] == 20
        eng1.journal.shutdown()
        eng2 = make_engine(lm, prefix_cache=True,
                           journal_dir=str(tmp_path))
        report = eng2.recover()
        assert eng2.prefix_cache.num_pages == 0  # cold start
        redelivered = {f.uid: f for f in report["redelivered"]}
        for uid, fin in out1.items():
            assert np.array_equal(redelivered[uid].tokens, fin.tokens)
        # The replayed engine serves (and caches) fresh work normally.
        out2 = _serve(eng2, [prompts[0]])
        assert len(out2) == 1
        eng2.check_balanced()
        eng2.journal.shutdown()
