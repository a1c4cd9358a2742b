"""KV-tier tests (ISSUE 15, dnn_tpu/kvtier): the radix prefix store,
block migration with the lease machine, and the serving integration.

Four families:
  * radix unit suite — insert/lookup/COW goldens against a FAKE
    allocator (no jax), refcount protection under eviction, leaf-LRU
    order, block-aligned vs ragged edges, concurrent admit/evict under
    the single-producer contract;
  * wire + lease — pack/unpack roundtrips (f32 / int8 / int4 nibble /
    bf16), corruption rejection, lease lifecycle incl. TTL expiry and
    the shm nonce proof; the KVLEASE protocol table both directions
    (the deleted-reclaim edge reproduces "blocks leak forever" as
    PRO002);
  * serving integration — radix admission parity with the uncached
    oracle (greedy AND seeded-sampled) through COW / full-hit /
    retire-time insertion, the row-capacity backoff golden, and
    export/adopt/stage cross-pool parity with zero leaked blocks;
  * donor death — a severed pull (chaos kv_migrate_fault / dead donor)
    must fall back loud (`kvtier_fallback`), re-prefill with ZERO
    token divergence, and leave the pool's block accounting at
    baseline.
"""

import threading

import numpy as np
import pytest

from dnn_tpu.kvtier.radix import RadixIndex
from dnn_tpu.kvtier.store import PrefixStore

BP = 4  # block_len for the pure-host suites


class FakeAllocator:
    """BlockAllocator-shaped double: refcount bookkeeping only."""

    def __init__(self):
        self.rc = {}

    def seed(self, blocks):
        for b in blocks:
            self.rc[b] = self.rc.get(b, 0) + 1

    def ref(self, blocks):
        for b in blocks:
            assert self.rc.get(b, 0) >= 1, f"ref on dead block {b}"
        for b in blocks:
            self.rc[b] += 1

    def free(self, blocks):
        for b in blocks:
            assert self.rc.get(b, 0) >= 1, f"free of dead block {b}"
        for b in blocks:
            self.rc[b] -= 1
            if self.rc[b] == 0:
                del self.rc[b]


def toks(*vals):
    return np.asarray(vals, np.int32)


def seq(n, start=1):
    return np.arange(start, start + n, dtype=np.int32)


# ----------------------------------------------------------------------
# radix unit suite
# ----------------------------------------------------------------------

def test_radix_insert_lookup_golden():
    ix = RadixIndex(BP, capacity=16)
    t = seq(12)  # 3 full chunks
    created, evicted = ix.insert(t, [10, 11, 12])
    assert [n.block for n in created] == [10, 11, 12] and not evicted
    # full path match
    m, cow_n, cow = ix.match(t)
    assert [n.block for n in m] == [10, 11, 12]
    assert cow_n == 0 and cow is None
    # shorter prompt: only covering chunks match
    m, cow_n, cow = ix.match(seq(8))
    assert [n.block for n in m] == [10, 11]
    # the 9..12 chunk of the full path agrees with a ragged tail
    m, cow_n, cow = ix.match(seq(10))
    assert [n.block for n in m] == [10, 11]
    assert cow is not None and cow.block == 12 and cow_n == 2
    # divergent tail: no boundary agreement
    m, cow_n, cow = ix.match(np.concatenate([seq(8), toks(99, 98)]))
    assert [n.block for n in m] == [10, 11] and cow_n == 0


def test_radix_cow_boundary_picks_longest_agreement():
    ix = RadixIndex(BP, capacity=16)
    base = seq(4)
    ix.insert(np.concatenate([base, toks(5, 6, 90, 91)]), [1, 2])
    ix.insert(np.concatenate([base, toks(5, 6, 7, 92)]), [1, 3])
    # query agrees with the second child on 3 tokens, first on 2
    m, cow_n, cow = ix.match(np.concatenate([base, toks(5, 6, 7, 8)]))
    assert [n.block for n in m] == [1]
    assert cow.block == 3 and cow_n == 3


def test_radix_insert_reuses_existing_nodes():
    ix = RadixIndex(BP, capacity=16)
    ix.insert(seq(8), [1, 2])
    created, _ = ix.insert(seq(12), [91, 92, 3])  # blocks 91/92 ignored
    assert [n.block for n in created] == [3]
    m, _n, _c = ix.match(seq(12))
    assert [n.block for n in m] == [1, 2, 3]


def test_radix_leaf_lru_eviction_order_scan_resistant():
    """Inserted nodes PARK at the LRU end (newest park evicts first —
    a novel-prompt scan cycles its own nodes through the eviction
    slot); only a MATCH promotes."""
    ix = RadixIndex(BP, capacity=16)
    ix.insert(seq(4, start=1), [1])
    ix.insert(seq(4, start=100), [2])
    ix.insert(seq(4, start=200), [3])
    ix.match(seq(4, start=1))    # touch 1 -> MRU
    v = ix.evict_lru_leaf()
    assert v.block == 3          # newest PARKED (never matched) first
    v = ix.evict_lru_leaf()
    assert v.block == 2
    v = ix.evict_lru_leaf()
    assert v.block == 1          # the matched node survives longest
    assert ix.evict_lru_leaf() is None


def test_radix_interior_nodes_not_evictable():
    ix = RadixIndex(BP, capacity=16)
    ix.insert(seq(12), [1, 2, 3])
    assert ix.evict_lru_leaf().block == 3   # deepest leaf first
    assert ix.evict_lru_leaf().block == 2
    assert ix.evict_lru_leaf().block == 1


def test_radix_capacity_evicts_on_insert():
    ix = RadixIndex(BP, capacity=2)
    ix.insert(seq(8), [1, 2])
    created, evicted = ix.insert(seq(8, start=100), [3, 4])
    # made room by evicting the old path's leaves; never over capacity
    assert ix.n_nodes <= 2
    assert {n.block for n in evicted} <= {1, 2}
    # the path being inserted is protected from its own eviction
    assert [n.block for n in created][:1] == [3]


def test_store_refcount_protects_shared_blocks():
    a = FakeAllocator()
    a.seed([7, 8])  # the "slot" holds one ref each
    st = PrefixStore(a, BP, capacity=8)
    st.insert(seq(8), [7, 8])
    assert a.rc == {7: 2, 8: 2}   # slot + store
    assert st.evict_one() and st.evict_one()
    assert a.rc == {7: 1, 8: 1}   # eviction dropped ONLY store refs
    assert not st.evict_one()


def test_store_block_hit_accounting_and_origin():
    a = FakeAllocator()
    a.seed([1, 2])
    st = PrefixStore(a, BP, capacity=8)
    st.insert(seq(8), [1, 2], origin="adopted")
    hit = st.lookup(seq(8))
    assert hit.shared == [1, 2] and hit.origins == ["adopted"] * 2
    assert hit.remote_used(2, False) == 2
    # lookup has NO counter side effects — admission reports what it
    # actually reused (a truncated or failed admission counts nothing)
    assert st.block_hits == 0
    st.note_reuse(2, hit.remote_used(2, False))
    assert st.block_hits == 2 and st.remote_block_hits == 2
    # truncation: only the first block got used
    assert hit.remote_used(1, False) == 1
    miss = st.lookup(seq(8, start=500))
    assert miss.shared == [] and st.block_hits == 2


def test_store_full_hit_needs_hidden_row_and_alignment():
    a = FakeAllocator()
    a.seed([1, 2])
    st = PrefixStore(a, BP, capacity=8)
    lr = np.arange(5.0)
    st.insert(seq(8), [1, 2], hidden_rows={1: lr})
    assert st.lookup(seq(8)).hidden_row is lr          # aligned + row
    assert st.lookup(seq(7)).hidden_row is None        # ragged
    a2 = FakeAllocator()
    a2.seed([3])
    st2 = PrefixStore(a2, BP, capacity=8)
    st2.insert(seq(4), [3])                           # no hidden row
    assert st2.lookup(seq(4)).hidden_row is None


def test_store_concurrent_scrape_during_admit_evict():
    """The single-producer contract: one thread mutates (insert/evict)
    while scrape-side readers hammer the counters — no exceptions, no
    negative reads (the gauges are GIL-atomic int loads)."""
    a = FakeAllocator()
    st = PrefixStore(a, BP, capacity=32)
    stop = threading.Event()
    errs = []

    def scraper():
        while not stop.is_set():
            try:
                assert st.n_blocks >= 0
                assert st.block_hits >= 0
            except Exception as e:  # noqa: BLE001
                errs.append(e)
                return

    th = threading.Thread(target=scraper, daemon=True)
    th.start()
    for i in range(300):
        blocks = [1000 + i * 2, 1001 + i * 2]
        a.seed(blocks)
        st.insert(seq(8, start=i * 10 + 1), blocks)
        st.lookup(seq(8, start=i * 10 + 1))
        if i % 3 == 0:
            st.evict_one()
        a.free(blocks)  # the "slot" retires
    stop.set()
    th.join(timeout=5)
    assert not errs


# ----------------------------------------------------------------------
# wire codec + lease machine
# ----------------------------------------------------------------------

def test_pack_unpack_roundtrip_f32_int8_int4_bf16():
    from dnn_tpu.kvtier import migrate as M

    rng = np.random.default_rng(0)
    cases = [
        ("float32", rng.standard_normal((2, 2, 3, BP, 5),
                                        ).astype(np.float32)),
        ("int8", rng.integers(-127, 128, (2, 2, 3, BP, 5),
                              ).astype(np.int8)),
        ("int4", rng.integers(-8, 8, (2, 2, 3, BP, 5),
                              ).astype(np.int8)),
    ]
    import ml_dtypes

    cases.append(("bfloat16", rng.standard_normal(
        (2, 2, 3, BP, 5)).astype(ml_dtypes.bfloat16)))
    for name, arr in cases:
        pl = {"tokens": seq(2 * BP), "block_len": BP,
              "leaves": {"k": arr},
              "hidden_rows": {0: np.arange(7.0, dtype=np.float32)},
              "fingerprint": {"leaves": {
                  "k": [list(arr.shape), name]}}}
        back = M.unpack_blocks(M.pack_blocks(pl))
        np.testing.assert_array_equal(back["tokens"], pl["tokens"])
        if name == "bfloat16":
            np.testing.assert_array_equal(
                back["leaves"]["k"].view(np.uint16),
                arr.view(np.uint16))
        else:
            np.testing.assert_array_equal(back["leaves"]["k"], arr)
        np.testing.assert_array_equal(back["hidden_rows"][0],
                                      pl["hidden_rows"][0])
    # int4 ships nibble-packed: strictly under 1 byte/element on wire
    arr4 = cases[2][1]
    pl4 = {"tokens": seq(2 * BP), "block_len": BP,
           "leaves": {"k": arr4}, "hidden_rows": {},
           "fingerprint": {"leaves": {"k": [list(arr4.shape),
                                            "int4"]}}}
    wire4 = M.pack_blocks(pl4)
    pl8 = dict(pl4, fingerprint={"leaves": {"k": [list(arr4.shape),
                                                  "int8"]}})
    wire8 = M.pack_blocks(pl8)
    assert wire4.size < wire8.size
    # saves half the leaf bytes, modulo a few header bytes ("nibble")
    assert wire8.size - wire4.size >= arr4.size // 2 - 16


def test_unpack_rejects_garbage_and_truncation():
    from dnn_tpu.kvtier import migrate as M

    with pytest.raises(ValueError, match="bad magic"):
        M.unpack_blocks(np.frombuffer(b"nonsense bytes!!", np.uint8))
    pl = {"tokens": seq(BP), "block_len": BP,
          "leaves": {"k": np.zeros((1, 1, 1, BP, 2), np.float32)},
          "hidden_rows": {}, "fingerprint": {}}
    wire = M.pack_blocks(pl)
    with pytest.raises(ValueError, match="truncated"):
        M.unpack_blocks(wire[: wire.size - 8])


def test_lease_lifecycle_and_ttl_expiry():
    from dnn_tpu.kvtier import migrate as M

    lt = M.LeaseTable(ttl_s=30.0, use_shm=False)
    meta = lt.offer(b"payload-bytes")
    assert lt.fetch(meta["lease"]) == b"payload-bytes"
    assert lt.ack(meta["lease"]) and lt.n_leases == 0
    assert not lt.ack(meta["lease"])  # second ack: already gone
    # TTL expiry reclaims an abandoned offer (offered AND pulling)
    m2 = lt.offer(b"x" * 64)
    lt.fetch(m2["lease"])  # pulling
    assert lt.sweep(now=1e18) == 1
    with pytest.raises(KeyError):
        lt.fetch(m2["lease"])
    assert lt.n_leases == 0


def test_lease_shm_rung_nonce_proof():
    from dnn_tpu.kvtier import migrate as M

    pub = M.publish_shm(b"block-bytes")
    if pub is None:
        pytest.skip("no POSIX shm on this platform")
    name, nonce, seg = pub
    try:
        assert M.attach_shm(name, nonce, 11) == b"block-bytes"
        with pytest.raises(ValueError, match="nonce"):
            M.attach_shm(name, "00" * 16, 11)
    finally:
        seg.close()
        seg.unlink()


def test_kvlease_machine_clean_and_both_directions():
    """The declared table is sound, and deleting the expired state's
    reclaim edge reproduces 'staged blocks leak forever' as a PRO002
    model failure (the issue's required direction); deleting the
    expire edges strands `expired` as unreachable (PRO001)."""
    import dataclasses

    from dnn_tpu.analysis.protocol import KVLEASE, check_machine

    assert check_machine(KVLEASE) == []
    no_reclaim = dataclasses.replace(
        KVLEASE, edges=tuple(e for e in KVLEASE.edges
                             if e.event != "lease_reclaim"))
    rules = {f.rule for f in check_machine(no_reclaim)}
    assert "PRO002" in rules
    no_expire = dataclasses.replace(
        KVLEASE, edges=tuple(e for e in KVLEASE.edges
                             if e.event not in ("lease_expire",
                                                "lease_reclaim")))
    rules = {f.rule for f in check_machine(no_expire)}
    assert "PRO001" in rules


def test_chaos_plan_gains_donor_kill_fault():
    from dnn_tpu.chaos.plan import Fault, FaultPlan

    plan = FaultPlan(faults=(
        Fault(kind="kill_stage", target="node2", at_s=15.0),
        Fault(kind="kill_donor", target="r0", at_s=12.0)))
    kinds = [f.kind for f in plan.process_faults()]
    assert "kill_donor" in kinds
    # schema roundtrip (plans ship as JSON)
    back = FaultPlan.from_dict(plan.to_dict())
    assert back == plan
    # the in-process migration fault parses too
    p2 = FaultPlan.from_dict({"faults": [
        {"kind": "kv_migrate_fault", "at_n": 0}]})
    assert p2.inprocess_faults()[0].kind == "kv_migrate_fault"


def test_directory_observe_locate_forget():
    from dnn_tpu.kvtier.directory import PrefixDirectory

    d = PrefixDirectory(BP, cap=64)
    t = seq(3 * BP)
    d.observe(t, "r0")
    loc = d.locate(t)
    assert loc.replica == "r0" and loc.n_blocks == 3
    # deeper knowledge wins; ragged tails fall back to full blocks
    assert d.locate(np.concatenate([t, toks(99)])).n_blocks == 3
    assert d.locate(t[: 2 * BP]).n_blocks == 2
    assert d.locate(seq(BP, start=900)) is None
    # latest claim wins
    d.observe(t, "r1")
    assert d.locate(t).replica == "r1"
    assert d.forget("r1") == 3
    assert d.locate(t) is None


# ----------------------------------------------------------------------
# serving integration (jax from here down)
# ----------------------------------------------------------------------

SBP = 8  # serving block_len


@pytest.fixture(scope="module")
def served():
    import jax

    from dnn_tpu.models import gpt

    cfg = gpt.GPTConfig(block_size=64, vocab_size=256, n_layer=2,
                        n_head=4, n_embd=64)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    return cfg, prepared


def _radix_pool(served, **kw):
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg, prepared = served
    args = dict(slots=2, max_len=64, prompt_pad=16, kv="paged",
                paged_blocks=24, block_len=SBP, prefix_cache=16)
    args.update(kw)
    return ContinuousBatcher(cfg, prepared, **args)


def _oracle(served, prompt, max_new, **sub):
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg, prepared = served
    ref = ContinuousBatcher(cfg, prepared, slots=1, max_len=64,
                            prompt_pad=16)
    r = ref.submit(prompt, max_new, **sub)
    return ref.drain()[r]


def test_cow_boundary_saves_chunks_with_exact_parity(served):
    srv = _radix_pool(served)
    sys_p = seq(21)  # 2 full blocks + 5 ragged (bp=8)
    a = np.concatenate([sys_p, toks(30, 31, 32)])
    b = np.concatenate([sys_p, toks(40, 41, 42, 43)])
    ra = srv.submit(a, max_new_tokens=5)
    srv.drain()
    c0 = srv.prefill_chunks_run
    rb = srv.submit(b, max_new_tokens=5, seed=3, temperature=0.8)
    out = srv.drain()
    # cold = 2 chunks (25 tokens / pad 16); the COW boundary resumes
    # mid-block at the divergence -> ONE chunk
    assert srv.prefill_chunks_run - c0 == 1
    assert srv.prefix_hits == 1
    np.testing.assert_array_equal(out[ra], _oracle(served, a, 5))
    np.testing.assert_array_equal(
        out[rb], _oracle(served, b, 5, seed=3, temperature=0.8))


def test_block_aligned_full_hit_zero_chunks(served):
    srv = _radix_pool(served)
    p = seq(16)  # exactly 2 blocks, NOT chunk-count aligned cases too
    r1 = srv.submit(p, max_new_tokens=4)
    srv.drain()
    c0 = srv.prefill_chunks_run
    r2 = srv.submit(p, max_new_tokens=4)
    out = srv.drain()
    assert srv.prefill_chunks_run == c0  # zero chunks: stored hidden row
    np.testing.assert_array_equal(out[r1], out[r2])
    np.testing.assert_array_equal(out[r2], _oracle(served, p, 4))


def test_ragged_same_prompt_recomputes_only_tail(served):
    srv = _radix_pool(served)
    p = seq(19)  # 2 blocks + 3 ragged
    srv.submit(p, max_new_tokens=4)
    srv.drain()
    c0 = srv.prefill_chunks_run
    r2 = srv.submit(p, max_new_tokens=4, seed=9, temperature=1.0)
    out = srv.drain()
    assert srv.prefill_chunks_run - c0 == 1  # the ragged tail chunk
    np.testing.assert_array_equal(
        out[r2], _oracle(served, p, 4, seed=9, temperature=1.0))


def test_retire_time_insertion_serves_chat_followup(served):
    srv = _radix_pool(served)
    t1 = seq(16)
    rt = srv.submit(t1, max_new_tokens=8)
    o = srv.drain()
    follow = np.concatenate([t1, o[rt].astype(np.int32), toks(5, 6, 7)])
    c0 = srv.prefill_chunks_run
    rf = srv.submit(follow, max_new_tokens=4)
    out = srv.drain()
    cold_chunks = -(-len(follow) // 16)
    assert srv.prefill_chunks_run - c0 < cold_chunks
    np.testing.assert_array_equal(out[rf], _oracle(served, follow, 4))


def test_row_capacity_backoff_near_full_row(served):
    """A prompt near max_len whose resume point is unaligned: the
    chunk loop must round the resume down (never overhang the
    transient row — a clamped dynamic update corrupts silently), with
    parity intact."""
    srv = _radix_pool(served, slots=1, paged_blocks=32)
    base = seq(21)  # ragged boundary -> mid-block resume candidates
    long_a = np.concatenate([base, seq(34, start=100)])  # 55 tokens
    long_b = np.concatenate([base, seq(37, start=200)])  # 58 tokens
    ra = srv.submit(long_a, max_new_tokens=3)
    srv.drain()
    rb = srv.submit(long_b, max_new_tokens=3)
    out = srv.drain()
    np.testing.assert_array_equal(out[ra],
                                  _oracle(served, long_a, 3))
    np.testing.assert_array_equal(out[rb],
                                  _oracle(served, long_b, 3))


def test_export_adopt_parity_and_block_accounting(served):
    srv = _radix_pool(served)
    ado = _radix_pool(served)
    p = seq(16)
    r = srv.submit(p, max_new_tokens=6, seed=7, temperature=0.9)
    want = srv.drain()[r]
    payload = srv.kvtier_export(p)
    assert payload["leaves"]["k"].shape[1] == 2  # 2 blocks
    used_before = ado._allocator.n_used
    assert ado.kvtier_adopt(payload) == 2
    assert ado._allocator.n_used == used_before + 2  # store-held only
    c0 = ado.prefill_chunks_run
    g = ado.submit(p, max_new_tokens=6, seed=7, temperature=0.9)
    got = ado.drain()[g]
    assert ado.prefill_chunks_run == c0  # full hit off adopted blocks
    np.testing.assert_array_equal(got, want)
    # cross-replica accounting: both hits were adopted-origin
    assert ado._prefix_store.remote_block_hits == 2
    assert ado._kvtier_remote_ratio_read() == 1.0
    # re-adopting the same payload is a dedup no-op
    assert ado.kvtier_adopt(payload) == 0


def test_adopt_rejects_geometry_mismatch(served):
    srv = _radix_pool(served)
    other = _radix_pool(served, kv_dtype="int8")
    p = seq(16)
    srv.submit(p, max_new_tokens=2)
    srv.drain()
    payload = srv.kvtier_export(p)
    with pytest.raises(ValueError, match="geometry mismatch"):
        other.kvtier_adopt(payload)


def test_int8_blocks_migrate_as_is(served):
    d8 = _radix_pool(served, kv_dtype="int8")
    a8 = _radix_pool(served, kv_dtype="int8")
    p = seq(16)
    r = d8.submit(p, max_new_tokens=5)
    want = d8.drain()[r]
    payload = d8.kvtier_export(p)
    assert set(payload["leaves"]) == {"k", "v", "ks", "vs"}
    from dnn_tpu.kvtier import migrate as M

    wire = M.pack_blocks(payload)
    a8.kvtier_adopt(M.unpack_blocks(wire))
    g = a8.submit(p, max_new_tokens=5)
    np.testing.assert_array_equal(a8.drain()[g], want)


def test_stage_prefix_then_admission_hits(served):
    srv = _radix_pool(served)
    p = seq(24)
    stats = srv.stage_prefix(p)
    assert stats["staged_blocks"] == 3
    # idempotent: a second stage computes nothing
    assert srv.stage_prefix(p)["staged_blocks"] == 0
    c0 = srv.prefill_chunks_run
    r = srv.submit(p, max_new_tokens=4)
    out = srv.drain()
    assert srv.prefill_chunks_run == c0  # block-aligned full hit
    np.testing.assert_array_equal(out[r], _oracle(served, p, 4))


def test_donor_death_mid_migration_zero_divergence_zero_leaks(served):
    """The chaos leg, in-process: the donor dies between lease and
    fetch (expired lease), the adopter's pull fails, and the follow-up
    admission re-prefills with identical tokens and baseline block
    accounting — nothing adopted, nothing leaked."""
    from dnn_tpu.kvtier import migrate as M

    donor = _radix_pool(served)
    ado = _radix_pool(served)
    p = seq(16)
    r = donor.submit(p, max_new_tokens=5)
    want = donor.drain()[r]
    payload = donor.kvtier_export(p)
    lt = M.LeaseTable(ttl_s=30.0, use_shm=False)
    meta = lt.offer(M.pack_blocks(payload).tobytes())
    lt.sweep(now=1e18)  # the donor's TTL fires: lease expired

    class DeadDonorClient:
        def kv_lease(self, tokens, timeout=None):
            return dict(meta)  # the offer raced the death

        def kv_fetch(self, lease_id, timeout=None):
            raise KeyError(lease_id)  # donor gone / lease reclaimed

        def kv_ack(self, lease_id, timeout=None):
            raise ConnectionError("donor dead")

    used0 = ado._allocator.n_used
    hw0 = ado._allocator.high_water
    with pytest.raises(Exception):
        M.pull_blocks(DeadDonorClient(), p)
    # nothing adopted, nothing leaked: accounting untouched
    assert ado._allocator.n_used == used0
    assert ado._allocator.high_water == hw0
    assert ado._prefix_store.n_blocks == 0
    # the re-prefill produces the identical stream
    g = ado.submit(p, max_new_tokens=5)
    np.testing.assert_array_equal(ado.drain()[g], want)


def test_chaos_kv_migrate_fault_severs_pull_deterministically():
    from dnn_tpu.chaos.inject import Injector
    from dnn_tpu.chaos.plan import FaultPlan

    inj = Injector(FaultPlan.from_dict(
        {"faults": [{"kind": "kv_migrate_fault", "at_n": 1,
                     "count": 1}]}))
    inj.kv_migrate()  # n=0: clean
    with pytest.raises(ConnectionError, match="donor death"):
        inj.kv_migrate()  # n=1: severed
    inj.kv_migrate()  # n=2: clean again — exactly one firing


def test_kvput_inbox_ttl_sweep(served):
    """Satellite: staged kvput handoffs expire — an abandoned prefill
    cannot pin its payload forever (kvput_expired flight event)."""
    import time as _time

    from dnn_tpu import obs
    from dnn_tpu.runtime.lm_server import LMServer

    cfg, prepared = served
    srv = LMServer(cfg, prepared, slots=2, max_len=64, prompt_pad=16,
                   kv_handoff_ttl_s=5.0)
    try:
        rec = obs.flight.recorder()
        srv._kv_handoff["fresh"] = ({"prompt_len": 4},
                                    _time.monotonic())
        srv._kv_handoff["stale"] = ({"prompt_len": 9},
                                    _time.monotonic() - 99.0)
        srv._sweep_kv_handoffs()
        assert "fresh" in srv._kv_handoff
        assert "stale" not in srv._kv_handoff
        evs = [e for e in rec.events(kind="kvput_expired")
               if e.get("key") == "stale"]
        assert evs and evs[-1]["prompt_len"] == 9
    finally:
        srv.close()


def test_worker_control_op_runs_on_busy_pool(served):
    """Control ops (the kvtier seam) apply between steps even while
    slots decode — and fail fast once the worker is dead."""
    from dnn_tpu.runtime.lm_server import LMServer

    cfg, prepared = served
    srv = LMServer(cfg, prepared, slots=2, max_len=64, prompt_pad=16,
                   kv="paged", paged_blocks=24, block_len=SBP,
                   prefix_cache=16)
    try:
        fut = srv.worker.submit(seq(8), 16, None)
        cfut = srv.worker.submit_control(
            lambda: srv.batcher.stage_prefix(seq(16, start=100)))
        stats = cfut.result(timeout=30)
        assert stats["staged_blocks"] == 2
        assert fut.result(timeout=30) is not None
    finally:
        srv.close()
    dead = srv.worker.submit_control(lambda: 1)
    with pytest.raises(Exception):
        dead.result(timeout=5)
