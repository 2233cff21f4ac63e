"""Paged KV/state cache: allocator invariants (property-tested), paged==dense
decode equivalence on random request mixes, eviction/recompute, defrag,
byte-accurate traffic accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, strategies as st

from repro.configs import get_config, reduced_config
from repro.core import EnergyModel
from repro.hw import H200_SXM
from repro.models import (
    decode_step,
    decode_step_paged,
    init_cache,
    init_paged_cache,
    init_params,
    kv_cache_bytes_per_token,
    paged_layout,
    prefill,
)
from repro.serving import (
    BlockAllocator,
    ClockController,
    Cluster,
    NULL_PAGE,
    ServingEngine,
)
from repro.training import make_prompts


_CACHE = {}


def _model():
    """Module-cached model: property bodies can't take pytest fixtures (the
    degraded _propcheck wrapper hides the signature), so both the fixture
    and @given-decorated tests share this."""
    if "m" not in _CACHE:
        cfg = reduced_config("gemma-2b")
        _CACHE["m"] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return _CACHE["m"]


@pytest.fixture(scope="module")
def setup():
    return _model()


# --------------------------------------------------------------- allocator
class TestAllocatorProperties:
    @settings(max_examples=30, deadline=None)
    @given(
        num_blocks=st.integers(min_value=1, max_value=64),
        seed=st.integers(min_value=0, max_value=10_000),
    )
    def test_random_alloc_free_traffic(self, num_blocks, seed):
        """Random alloc/free interleavings: no block is ever handed out
        twice, the ledger always balances, and freeing everything returns
        the allocator to a full free list."""
        rng = np.random.default_rng(seed)
        alloc = BlockAllocator(num_blocks, block_size=8)
        held = {}
        uid = 0
        for _ in range(100):
            if held and rng.random() < 0.45:
                owner = int(rng.choice(list(held)))
                alloc.free(held.pop(owner), owner)
            else:
                n = int(rng.integers(1, max(num_blocks // 2, 1) + 1))
                if alloc.can_alloc(n):
                    held[uid] = alloc.alloc(n, uid)
                    uid += 1
                else:
                    with pytest.raises(MemoryError):
                        alloc.alloc(n, uid)
            live = [b for blocks in held.values() for b in blocks]
            assert len(live) == len(set(live)), "double allocation"
            assert all(1 <= b <= num_blocks for b in live), "null/oob page leaked"
            assert alloc.free_blocks + len(live) == num_blocks
            assert alloc.used_blocks == len(live)
            alloc.assert_invariants()
        for owner, blocks in list(held.items()):
            alloc.free(blocks, owner)
        assert alloc.free_blocks == num_blocks, "free did not return all blocks"
        alloc.assert_invariants()

    def test_zero_size_edges(self):
        """alloc(0) and blocks_for_tokens(0) are well-defined no-ops."""
        alloc = BlockAllocator(4, 8)
        assert alloc.alloc(0, owner=1) == []
        assert alloc.blocks_for_tokens(0) == 0
        assert alloc.free_blocks == 4 and alloc.used_blocks == 0
        alloc.assert_invariants()

    @settings(max_examples=30, deadline=None)
    @given(
        num_blocks=st.integers(min_value=2, max_value=32),
        seed=st.integers(min_value=0, max_value=10_000),
    )
    def test_retain_release_sharing_traffic(self, num_blocks, seed):
        """Random retain/release interleavings on top of alloc/free: the
        refcount ledger balances at every step, a page dies only when its
        last reference goes, and draining everything empties the pool."""
        rng = np.random.default_rng(seed)
        alloc = BlockAllocator(num_blocks, block_size=8)
        refs = []                               # (block, owner) one per ref
        uid = 0
        for _ in range(120):
            r = rng.random()
            if refs and r < 0.35:
                i = int(rng.integers(len(refs)))
                block, owner = refs.pop(i)
                alloc.release(block, owner)
            elif refs and r < 0.6:
                block, _ = refs[int(rng.integers(len(refs)))]
                uid += 1
                alloc.retain(block, uid)
                refs.append((block, uid))
            else:
                uid += 1
                got = alloc.alloc_one(uid)
                if got is None:
                    assert alloc.free_blocks == 0
                else:
                    refs.append((got, uid))
            alloc.assert_invariants()
            live = {b for b, _ in refs}
            assert alloc.used_blocks == len(live)
            for b in live:
                assert alloc.refcount(b) == sum(1 for bb, _ in refs if bb == b)
                assert alloc.is_shared(b) == (alloc.refcount(b) > 1)
        for block, owner in refs:
            alloc.release(block, owner)
        alloc.assert_invariants()
        assert alloc.used_blocks == 0
        with pytest.raises(ValueError, match="retain of unallocated"):
            alloc.retain(1, owner=0)

    def test_defrag_remaps_shared_blocks_once(self):
        """A defrag mapping names each live page exactly once, shared or
        not, and every co-owner of a shared page survives on the new id."""
        alloc = BlockAllocator(8, 8)
        a = alloc.alloc(3, owner=1)             # ids 1..3
        b = alloc.alloc(2, owner=2)             # ids 4..5
        alloc.retain(a[2], owner=2)             # a[2] shared by 1 and 2
        alloc.free([a[0]], 1)                   # fragment the id space
        alloc.free([b[0]], 2)
        mapping = alloc.defrag()
        assert sorted(mapping) == sorted([a[1], a[2], b[1]])
        assert sorted(mapping.values()) == [1, 2, 3]
        assert len([old for old in mapping if old == a[2]]) == 1
        shared_new = mapping[a[2]]
        assert alloc.refcount(shared_new) == 2
        assert sorted(alloc.owners(shared_new)) == [1, 2]
        alloc.assert_invariants()

    def test_double_free_and_wrong_owner_raise(self):
        alloc = BlockAllocator(4, 8)
        blocks = alloc.alloc(2, owner=7)
        with pytest.raises(ValueError, match="owned by"):
            alloc.free(blocks, owner=8)
        alloc.free(blocks, owner=7)
        with pytest.raises(ValueError, match="double free"):
            alloc.free(blocks, owner=7)

    def test_never_hands_out_null_page(self):
        alloc = BlockAllocator(3, 8)
        assert sorted(alloc.alloc(3, owner=0)) == [1, 2, 3]
        assert NULL_PAGE == 0

    @settings(max_examples=20, deadline=None)
    @given(
        num_blocks=st.integers(min_value=2, max_value=40),
        seed=st.integers(min_value=0, max_value=1000),
    )
    def test_defrag_compacts_and_preserves_ownership(self, num_blocks, seed):
        rng = np.random.default_rng(seed)
        alloc = BlockAllocator(num_blocks, 8)
        held = {}
        for uid in range(rng.integers(1, 4)):
            n = int(rng.integers(1, max(num_blocks // 3, 1) + 1))
            if alloc.can_alloc(n):
                held[uid] = alloc.alloc(n, uid)
        # free a random subset to fragment the id space
        for uid in list(held):
            if rng.random() < 0.5:
                alloc.free(held.pop(uid), uid)
        used_before = alloc.used_blocks
        mapping = alloc.defrag()
        assert sorted(mapping.values()) == list(range(1, used_before + 1))
        assert alloc.used_blocks == used_before
        for uid, blocks in held.items():
            remapped = sorted(mapping[b] for b in blocks)
            assert alloc.owned_by(uid) == remapped
        # compacted ids are immediately re-allocatable without collision
        extra = alloc.alloc(alloc.free_blocks, owner=999)
        assert len(set(extra) | set(mapping.values())) == alloc.num_blocks


# ---------------------------------------------------- paged == dense decode
class TestPagedDenseEquivalence:
    @settings(max_examples=5, deadline=None)
    @given(
        n_requests=st.integers(min_value=2, max_value=6),
        seed=st.integers(min_value=0, max_value=50),
        tight=st.booleans(),
    )
    def test_engine_outputs_bit_for_bit(self, n_requests, seed, tight):
        """Random request mixes through the colocated engine: the paged
        path (continuous batching, block growth, preemption under a tight
        budget) must produce token-for-token identical greedy outputs."""
        cfg, params = _model()
        prompts = make_prompts(cfg, n_requests, 2, 12, seed=seed)

        dense = ServingEngine(cfg, params, max_batch=3, max_seq_len=64)
        rd = [dense.submit(p, max_new_tokens=6) for p in prompts]
        dense.run_to_completion()

        # tight budget: fewer blocks than the slots' worst case, forcing the
        # allocator-gated admission (and possibly eviction) paths
        kv_blocks = 8 if tight else 24
        paged = ServingEngine(
            cfg, params, max_batch=3, max_seq_len=64,
            paged=True, kv_block_size=8, kv_blocks=kv_blocks,
        )
        rp = [paged.submit(p, max_new_tokens=6) for p in prompts]
        paged.run_to_completion(max_steps=2000)

        assert all(r.done for r in rp)
        for a, b in zip(rd, rp):
            assert a.output == b.output
        assert paged.pool.allocator.used_blocks == 0  # all blocks returned

    def test_hybrid_paged_pool_serves_the_dense_tokens(self):
        """granite's reduced hybrid (Mamba2 state beside attention KV): the
        paged pool (KV in pages, state slot-indexed) serves the dense pool's
        tokens, and both serve what exact-length prefill and greedy decode
        of the model give, though every prompt is padded to its bucket."""
        cfg = reduced_config("granite-4.0-h-micro")
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = make_prompts(cfg, 4, 5, 30, seed=4)
        assert any(len(p) not in (32, 64) for p in prompts)

        def greedy(p, n):
            lg, cache, ln = prefill(params, cfg, jnp.asarray(p[None]),
                                    init_cache(cfg, 1, 64))
            out = []
            for _ in range(n):
                out.append(int(jnp.argmax(lg[0])))
                lg, cache, ln = decode_step(params, cfg, jnp.asarray(out[-1:]), cache, ln)
            return out

        dense = ServingEngine(cfg, params, max_batch=3, max_seq_len=64)
        rd = [dense.submit(p, max_new_tokens=6) for p in prompts]
        dense.run_to_completion()
        paged = ServingEngine(cfg, params, max_batch=3, max_seq_len=64,
                              paged=True, kv_block_size=8, kv_blocks=24)
        rp = [paged.submit(p, max_new_tokens=6) for p in prompts]
        paged.run_to_completion(max_steps=2000)
        for p, a, b in zip(prompts, rd, rp):
            assert a.output == b.output == greedy(p, 6)

    @staticmethod
    def _migrated(cfg, params, B=2, L_max=32, bs=8):
        """Two prefilled prompts laid into a dense cache and, page by page,
        into a paged one: (dense, paged, tables, lengths, first tokens)."""
        nb = L_max // bs
        prompts = [np.arange(1, 6, dtype=np.int32), np.arange(2, 12, dtype=np.int32)]

        dense = init_cache(cfg, B, L_max)
        paged = init_paged_cache(cfg, B, 1 + B * nb, bs)
        layout = paged_layout(cfg)
        tables = np.zeros((B, nb), np.int32)
        next_page = 1
        lengths = np.zeros(B, np.int32)
        toks = np.zeros(B, np.int32)

        for b, p in enumerate(prompts):
            c1 = init_cache(cfg, 1, L_max)
            lg, c1, _ = prefill(params, cfg, jnp.asarray(p[None]), c1)
            toks[b] = int(np.argmax(np.asarray(lg)[0]))
            lengths[b] = len(p)
            dense = jax.tree.map(
                lambda big, small, _b=b: jax.lax.dynamic_update_slice_in_dim(
                    big, small, _b, axis=1),
                dense, c1)
            need = -(-(len(p) + 1) // bs)
            pm = np.zeros(nb, np.int32)
            pm[:need] = np.arange(next_page, next_page + need)
            tables[b, :need] = pm[:need]
            next_page += need

            def scat(big, small, is_paged, _b=b, _pm=jnp.asarray(pm)):
                if is_paged:
                    rows = small[:, 0]
                    blocks = rows.reshape(rows.shape[0], nb, bs, *rows.shape[2:])
                    return big.at[:, _pm].set(blocks)
                return jax.lax.dynamic_update_slice_in_dim(big, small, _b, axis=1)

            paged = jax.tree.map(scat, paged, c1, layout)
        return dense, paged, jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(toks)

    def test_model_level_logits_match(self, setup):
        """decode_step_paged == decode_step on the same migrated prefill
        rows — paging is pure layout, checked at the logits level."""
        cfg, params = setup
        dense, paged, tables, lengths, tok = self._migrated(cfg, params)
        active = jnp.ones(2, bool)
        dl = pl_ = lengths
        dt_ = pt_ = tok
        for _ in range(3):
            lg_d, dense, dl = decode_step(params, cfg, dt_, dense, dl)
            lg_p, paged, pl_ = decode_step_paged(
                params, cfg, pt_, paged, pl_, active, tables)
            np.testing.assert_allclose(
                np.asarray(lg_d), np.asarray(lg_p), rtol=1e-5, atol=1e-5)
            dt_ = jnp.argmax(lg_d, -1).astype(jnp.int32)
            pt_ = jnp.argmax(lg_p, -1).astype(jnp.int32)

    @pytest.mark.parametrize("case", ["ragged", "full_slot", "inactive"])
    def test_model_level_step_writes_like_dense(self, setup, case):
        """One paged step on the migrated rows, bit for bit against the
        dense step (itself held to the full-buffer formula in
        test_models): the logits of every active slot; each active slot's
        pages, read through its table, hold the dense cache's rows; a slot
        at ``lengths == max_len`` changes no page of its own; an inactive
        slot writes its row to the null page and nothing else."""
        cfg, params = setup
        dense, paged, tables, lengths, tok = self._migrated(cfg, params)
        L_max = dense["stages"][0]["b0"]["k"].shape[2]
        if case == "full_slot":   # slot 0 holds all its pages (free, zero) and is full
            nb = tables.shape[1]
            tables = tables.at[0, 1:].set(1 + 2 * nb - jnp.arange(1, nb))
            lengths = lengths.at[0].set(L_max)
        active = jnp.array([case != "inactive", True])
        lg_d, dense2, _ = decode_step(params, cfg, tok, dense, lengths)
        lg_p, paged2, _ = decode_step_paged(params, cfg, tok, paged, lengths, active, tables)
        act = np.asarray(active)
        np.testing.assert_array_equal(np.asarray(lg_p)[act], np.asarray(lg_d)[act])

        def view(pages):  # (n, P, bs, ...) -> (n, B, nb*bs, ...)
            b, nb = tables.shape
            return pages[:, tables].reshape(pages.shape[0], b, -1, *pages.shape[3:])

        bs = paged["stages"][0]["b0"]["k"].shape[2]
        own = np.repeat(np.asarray(tables) != 0, bs, axis=1)   # (B, nb*bs)
        for d_after, p_before, p_after, is_paged in zip(
                *(jax.tree.leaves(t) for t in (dense2, paged, paged2, paged_layout(cfg)))):
            if not is_paged:
                np.testing.assert_array_equal(np.asarray(p_after), np.asarray(d_after))
                continue
            v_after, v_before = np.asarray(view(p_after)), np.asarray(view(p_before))
            for b in range(2):
                n, mine = int(lengths[b]), own[b]
                if case == "inactive" and b == 0:
                    np.testing.assert_array_equal(v_after[:, b, mine], v_before[:, b, mine])
                    np.testing.assert_array_equal(np.asarray(p_after[:, 0, n % bs]),
                                                  np.asarray(d_after[:, b, n]))
                    continue
                if case == "full_slot" and b == 0:
                    np.testing.assert_array_equal(v_after[:, b, mine], v_before[:, b, mine])
                mine = mine & (np.arange(mine.size) <= n)
                np.testing.assert_array_equal(v_after[:, b, mine],
                                              np.asarray(d_after[:, b, mine]))

    def test_cluster_paged_matches_dense_under_controller(self, setup):
        cfg, params = setup
        ctl = ClockController(EnergyModel(H200_SXM), get_config("gemma-2b"), mode="lock")
        prompts = make_prompts(cfg, 5, 4, 12, seed=3)
        cl_d = Cluster(cfg, params, decode_batch=2, max_seq_len=64,
                       prefill_chunk_tokens=64)
        rd = [cl_d.submit(p, max_new_tokens=6) for p in prompts]
        cl_d.run_to_completion()
        cl_p = Cluster(cfg, params, controller=ctl, decode_batch=4,
                       max_seq_len=64, prefill_chunk_tokens=64,
                       paged=True, kv_block_size=8, kv_blocks=16)
        rp = [cl_p.submit(p, max_new_tokens=6) for p in prompts]
        cl_p.run_to_completion()
        for a, b in zip(rd, rp):
            assert a.output == b.output

    def test_defrag_mid_run_is_invariant(self, setup):
        cfg, params = setup
        prompts = make_prompts(cfg, 4, 4, 12, seed=4)
        ref = ServingEngine(cfg, params, max_batch=4, max_seq_len=64,
                            paged=True, kv_block_size=8)
        rr = [ref.submit(p, max_new_tokens=8) for p in prompts]
        ref.run_to_completion()
        eng = ServingEngine(cfg, params, max_batch=4, max_seq_len=64,
                            paged=True, kv_block_size=8)
        rp = [eng.submit(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            eng.step()
        eng.pool.defrag()
        eng.run_to_completion()
        for a, b in zip(rr, rp):
            assert a.output == b.output

    def test_unservable_paged_request_raises_not_livelocks(self, setup):
        """A prompt needing more blocks than the pool owns can never be
        admitted — it must raise at the next tick (like the dense
        max_seq_len check), not leave can_admit() False forever while
        busy() spins."""
        cfg, params = setup
        cl = Cluster(cfg, params, decode_batch=2, max_seq_len=64,
                     prefill_chunk_tokens=64,
                     paged=True, kv_block_size=8, kv_blocks=3)
        cl.submit(np.arange(1, 30, dtype=np.int32), max_new_tokens=4)  # 33 tok > 24
        ok = cl.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
        with pytest.raises(ValueError, match="unservable even alone"):
            cl.step()
        done = cl.run_to_completion()
        assert [r.uid for r in done] == [ok.uid] and ok.done

        eng = ServingEngine(cfg, params, max_batch=2, max_seq_len=64,
                            paged=True, kv_block_size=8, kv_blocks=3)
        eng.submit(np.arange(1, 30, dtype=np.int32), max_new_tokens=4)
        with pytest.raises(ValueError, match="unservable even alone"):
            eng.step()

    def test_eviction_recompute_preserves_outputs(self, setup):
        """3 slots x 3-block worst case over a 4-block budget: admission
        succeeds (1 block each) but growth must preempt; recompute restores
        identical greedy outputs."""
        cfg, params = setup
        prompts = [np.arange(1, 8, dtype=np.int32) + i for i in range(3)]
        dense = ServingEngine(cfg, params, max_batch=3, max_seq_len=64)
        rd = [dense.submit(p, max_new_tokens=12) for p in prompts]
        dense.run_to_completion()
        paged = ServingEngine(cfg, params, max_batch=3, max_seq_len=64,
                              paged=True, kv_block_size=8, kv_blocks=4)
        rp = [paged.submit(p, max_new_tokens=12) for p in prompts]
        paged.run_to_completion(max_steps=2000)
        assert all(r.done for r in rp)
        assert sum(r.preemptions for r in rp) > 0
        for a, b in zip(rd, rp):
            assert a.output == b.output


# --------------------------------------------- prefix sharing == dense/paged
class TestPrefixCowEquivalence:
    @staticmethod
    def _run_waves(eng, waves, max_new=6):
        outs = []
        for wave in waves:
            reqs = [eng.submit(p, max_new_tokens=max_new) for p in wave]
            eng.run_to_completion(max_steps=4000)
            assert all(r.done for r in reqs)
            outs.append([r.output for r in reqs])
        return outs

    @staticmethod
    def _trunk_waves(cfg, seed):
        """Wave 1 seeds the index (registration happens at finish); wave 2
        reuses the trunk with random suffixes — 0-length suffix is an exact
        fork, which must COW-split the shared tail on first decode write."""
        rng = np.random.default_rng(seed)
        trunk = rng.integers(
            1, cfg.vocab_size - 1, size=int(rng.integers(10, 22))
        ).astype(np.int32)
        kids = [
            np.concatenate([trunk, rng.integers(
                1, cfg.vocab_size - 1, size=int(k)).astype(np.int32)])
            for k in rng.integers(0, 9, size=3)
        ]
        return [[trunk], kids]

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=50), tight=st.booleans())
    def test_cow_outputs_bit_for_bit(self, seed, tight):
        """Shared-trunk waves through dense, paged, and paged+COW engines:
        sharing (hits, suffix-only prefill, COW splits, index eviction
        under the tight budget) must never change a single output token."""
        cfg, params = _model()
        waves = self._trunk_waves(cfg, seed)

        dense = self._run_waves(
            ServingEngine(cfg, params, max_batch=3, max_seq_len=64), waves)
        kv_blocks = 10 if tight else 24
        plain = self._run_waves(ServingEngine(
            cfg, params, max_batch=3, max_seq_len=64,
            paged=True, kv_block_size=8, kv_blocks=kv_blocks), waves)
        cow_eng = ServingEngine(
            cfg, params, max_batch=3, max_seq_len=64,
            paged=True, kv_block_size=8, kv_blocks=kv_blocks,
            prefix_sharing=True)
        cow = self._run_waves(cow_eng, waves)

        assert cow == dense == plain
        ps = cow_eng.pool.prefix_stats
        assert ps.lookups == 4 and ps.registrations >= 1
        # at run end the only live pages are the index's retained ones
        alloc = cow_eng.pool.allocator
        alloc.assert_invariants()
        assert alloc.used_blocks == cow_eng.pool._prefix.held_blocks
        cow_eng.pool._prefix.clear()
        assert alloc.used_blocks == 0

    def test_defrag_mid_run_remaps_shared_exactly_once(self, setup):
        """Defrag while the index holds shared pages: the trie is remapped
        through the same old->new mapping (each entry exactly once) and
        outputs stay invariant."""
        cfg, params = setup
        waves = self._trunk_waves(cfg, seed=7)
        ref = self._run_waves(ServingEngine(
            cfg, params, max_batch=3, max_seq_len=64,
            paged=True, kv_block_size=8, kv_blocks=24,
            prefix_sharing=True), waves)

        eng = ServingEngine(cfg, params, max_batch=3, max_seq_len=64,
                            paged=True, kv_block_size=8, kv_blocks=24,
                            prefix_sharing=True)
        outs = [self._run_waves(eng, waves[:1])[0]]
        idx = eng.pool._prefix
        held_before = sorted(idx.blocks())
        assert held_before, "wave 1 registered nothing"
        reqs = [eng.submit(p, max_new_tokens=6) for p in waves[1]]
        for _ in range(2):
            eng.step()
        eng.pool.defrag()
        held_after = sorted(idx.blocks())
        assert len(held_after) == len(held_before) == idx.held_blocks
        assert len(set(held_after)) == len(held_after), \
            "defrag remapped a shared block twice (id collision)"
        eng.pool.allocator.assert_invariants()
        eng.run_to_completion(max_steps=4000)
        assert all(r.done for r in reqs)
        outs.append([r.output for r in reqs])
        assert outs == ref


# ------------------------------------------------------ traffic and energy
class TestTrafficAccounting:
    def test_bytes_and_joules_conserve_per_request(self, setup):
        cfg, params = setup
        ctl = ClockController(EnergyModel(H200_SXM), get_config("gemma-2b"), mode="lock")
        cl = Cluster(cfg, params, controller=ctl, decode_batch=3,
                     max_seq_len=64, prefill_chunk_tokens=64,
                     paged=True, kv_block_size=8, kv_blocks=24)
        reqs = [cl.submit(p, max_new_tokens=5)
                for p in make_prompts(cfg, 5, 4, 12, seed=5)]
        cl.run_to_completion()
        s = cl.decode_stats
        assert s.decode_j > 0 and s.decode_read_bytes > 0 and s.decode_write_bytes > 0
        np.testing.assert_allclose(s.decode_j, sum(r.decode_j for r in reqs), rtol=1e-9)
        assert s.decode_read_bytes == sum(r.decode_read_bytes for r in reqs)
        assert s.decode_write_bytes == sum(r.decode_write_bytes for r in reqs)

    def test_block_reads_match_table_occupancy(self, setup):
        """The counter's block reads must equal the sum over steps of the
        blocks each active request's table spans — the block-accurate
        definition of decode traffic."""
        cfg, params = setup
        bs = 8
        eng = ServingEngine(cfg, params, max_batch=2, max_seq_len=64,
                            paged=True, kv_block_size=bs, kv_blocks=16)
        req = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
        expected_blocks = 0
        length = len(req.prompt)
        while not req.done:
            done = eng.step()
            if eng.pool.occupancy() > 0 or done:
                expected_blocks += length // bs + 1
                length += 1
        assert eng.pool.traffic.block_reads == expected_blocks
        token_bytes = kv_cache_bytes_per_token(cfg)
        # every step also rewrote exactly one token of cache per layer
        assert eng.pool.traffic.block_writes >= eng.pool.traffic.steps
        assert eng.pool.traffic.write_bytes >= token_bytes * eng.pool.traffic.steps

    def test_dense_pool_keeps_shape_based_energy(self, setup):
        """No paging -> no traffic ledger; decode_j falls back to the
        energy/token estimate (seed behaviour, still covered by
        test_cluster.py)."""
        cfg, params = setup
        ctl = ClockController(EnergyModel(H200_SXM), get_config("gemma-2b"), mode="lock")
        cl = Cluster(cfg, params, controller=ctl, decode_batch=2,
                     max_seq_len=64, prefill_chunk_tokens=64)
        for p in make_prompts(cfg, 3, 4, 10, seed=6):
            cl.submit(p, max_new_tokens=4)
        cl.run_to_completion()
        s = cl.decode_stats
        assert s.decode_j > 0
        assert s.decode_read_bytes == 0 and s.decode_write_bytes == 0


# ------------------------------------------------------------ EOS satellite
class TestConfigurableEOS:
    def test_config_eos_stops_decode(self, setup):
        cfg, params = setup
        ref = ServingEngine(cfg, params, max_batch=1, max_seq_len=64)
        r0 = ref.submit(make_prompts(cfg, 1, 6, 10, seed=7)[0], max_new_tokens=8)
        ref.run_to_completion()
        assert len(r0.output) == 8          # default eos id 0 never sampled

        stop_tok = r0.output[3]             # first DECODE token to reuse as EOS
        cfg2 = dataclasses.replace(cfg, eos_token_id=stop_tok)
        eng = ServingEngine(cfg2, params, max_batch=1, max_seq_len=64)
        r1 = eng.submit(make_prompts(cfg, 1, 6, 10, seed=7)[0], max_new_tokens=8)
        eng.run_to_completion()
        stop_at = r0.output.index(stop_tok, 1) + 1
        assert r1.output == r0.output[:stop_at]

    def test_request_override_beats_config(self, setup):
        cfg, params = setup
        ref = ServingEngine(cfg, params, max_batch=1, max_seq_len=64)
        r0 = ref.submit(make_prompts(cfg, 1, 6, 10, seed=8)[0], max_new_tokens=8)
        ref.run_to_completion()
        eng = ServingEngine(cfg, params, max_batch=1, max_seq_len=64)
        r1 = eng.submit(make_prompts(cfg, 1, 6, 10, seed=8)[0], max_new_tokens=8)
        r1.eos_token_id = r0.output[1]
        eng.run_to_completion()
        stop_at = r0.output.index(r0.output[1], 1) + 1
        assert r1.output == r0.output[:stop_at]
