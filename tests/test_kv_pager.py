"""KVPager: page alloc/free/reuse accounting, spill/restore host tier,
optimistic admission, commit scatter layout."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.kv_pager import (KVPager, PageAllocationError, PagerConfig,
                                    commit_prefill)


def _pager(num_pages=17, page_size=4, num_slots=4, pages_per_slot=4):
    return KVPager(PagerConfig(num_pages=num_pages, page_size=page_size,
                               num_slots=num_slots,
                               pages_per_slot=pages_per_slot))


def test_alloc_free_roundtrip_accounting():
    p = _pager()
    assert p.num_free_pages == 16 and p.pages_in_use == 0
    slot, pages = p.alloc_slot(prompt_len=6, max_new_tokens=5)
    # 6-token prompt at P=4 → 2 pages now; 6+5-1=10 tokens → 3 total, 1 held
    assert len(pages) == 2
    assert p.pages_in_use == 2
    assert p.num_free_pages == 14
    assert p.slot_reserved[slot] == 1
    p.extend(slot, 9)                      # 9 tokens → 3rd page drawn
    assert p.pages_in_use == 3 and p.slot_reserved[slot] == 0
    p.free_slot(slot)
    assert p.pages_in_use == 0 and p.num_free_pages == 16
    assert p.num_free_slots == 4
    assert (p.page_tables[slot] == 0).all()   # back to scratch mapping


def test_page_exclusivity_and_reuse():
    p = _pager()
    s1, pg1 = p.alloc_slot(4, 1)
    s2, pg2 = p.alloc_slot(4, 1)
    assert not set(pg1) & set(pg2)
    assert 0 not in pg1 + pg2              # scratch page never handed out
    p.free_slot(s1)
    s3, pg3 = p.alloc_slot(8, 1)
    # LIFO free list: the freed page is reused first
    assert pg1[0] in pg3
    assert not set(pg3) & set(pg2)


def test_admission_respects_reservations():
    # 5 usable pages; first request reserves 4 (16 tokens worst case)
    p = _pager(num_pages=6, page_size=4, num_slots=2, pages_per_slot=4)
    s1, _ = p.alloc_slot(prompt_len=4, max_new_tokens=13)   # 16 tok → 4 pages
    assert p.slot_reserved[s1] == 3
    # one unreserved page left → an 8-token request must be refused
    assert not p.can_admit(prompt_len=5, max_new_tokens=4)
    assert p.can_admit(prompt_len=4, max_new_tokens=1)
    with pytest.raises(PageAllocationError):
        p.alloc_slot(prompt_len=5, max_new_tokens=4)
    # after the big request frees, admission succeeds again
    p.free_slot(s1)
    assert p.can_admit(prompt_len=5, max_new_tokens=4)


def test_over_capacity_request_rejected():
    p = _pager(pages_per_slot=2, page_size=4)   # 8-token slot capacity
    assert not p.can_admit(prompt_len=6, max_new_tokens=4)
    with pytest.raises(PageAllocationError):
        p.alloc_slot(6, 4)


def test_extend_cannot_outgrow_reservation():
    p = _pager()
    slot, _ = p.alloc_slot(prompt_len=4, max_new_tokens=1)  # exactly 1 page
    with pytest.raises(PageAllocationError):
        p.extend(slot, 5)


def test_truncate_releases_pages_back_to_reservation():
    p = _pager(page_size=4)
    slot, pages = p.alloc_slot(prompt_len=6, max_new_tokens=8)
    # 6+8-1 = 13 tokens → 4 pages total, 2 drawn now, 2 reserved
    assert p.slot_reserved[slot] == 2
    p.extend(slot, 11)                      # verify run crossed a boundary
    assert p.pages_in_use == 3 and p.slot_reserved[slot] == 1
    released = p.truncate(slot, 8)          # rejected drafts → roll back
    assert released == 1
    assert p.pages_in_use == 2
    assert p.slot_reserved[slot] == 2       # page returned to the reserve
    assert int(p.slot_len[slot]) == 8
    assert p.page_tables[slot, 2] == 0      # table entry back to scratch
    p.extend(slot, 13)                      # rollback never blocks re-extend
    assert p.pages_in_use == 4
    p.free_slot(slot)
    assert p.pages_in_use == 0 and p.num_free_pages == 16


def test_truncate_within_page_keeps_mapping():
    p = _pager(page_size=4)
    slot, _ = p.alloc_slot(prompt_len=5, max_new_tokens=4)
    p.extend(slot, 7)
    assert p.truncate(slot, 6) == 0         # same page: nothing released
    assert int(p.slot_len[slot]) == 6
    p.free_slot(slot)


def test_truncate_guards():
    p = _pager(page_size=4)
    slot, _ = p.alloc_slot(prompt_len=6, max_new_tokens=6)
    p.slot_committed[slot] = 6              # prompt fully resident
    with pytest.raises(PageAllocationError):
        p.truncate(slot, 5)                 # below the prompt watermark
    with pytest.raises(PageAllocationError):
        p.truncate(slot, 99)                # growth is not a truncation
    with pytest.raises(PageAllocationError):
        p.truncate(slot + 1, 4)             # inactive slot
    # aliased/pinned pages are never rolled back: simulate a second owner
    # on the tail page (a pin) and ask for a rollback that would free it
    p.extend(slot, 11)                      # draws the 3rd page
    tail = p.slot_pages[slot][-1]
    p.page_ref[tail] += 1
    with pytest.raises(PageAllocationError):
        p.truncate(slot, 8)
    assert int(p.slot_len[slot]) == 11      # guard fired before mutation
    p.page_ref[tail] -= 1
    assert p.truncate(slot, 8) == 1


def test_double_free_and_underflow_raise():
    p = _pager()
    slot, pages = p.alloc_slot(prompt_len=4, max_new_tokens=1)
    p.free_slot(slot)
    before = (len(p.free_pages), len(set(p.free_pages)))
    with pytest.raises(PageAllocationError):
        p.free_slot(slot)                   # double free of the slot
    with pytest.raises(RuntimeError):
        p._release_page(pages[0])           # refcount underflow
    # the failed frees never pushed a duplicate onto the free list
    assert (len(p.free_pages), len(set(p.free_pages))) == before
    assert len(p.free_pages) == len(set(p.free_pages))


def test_spill_restore_roundtrip_accounting():
    p = _pager(page_size=4)
    slot, _ = p.alloc_slot(prompt_len=6, max_new_tokens=7)   # 12 tok, 3 pages
    p.slot_committed[slot] = 6
    p.extend(slot, 9)                       # 3rd page drawn, 0 reserved left
    pages_before = list(p.slot_pages[slot])
    assert p.peek_spill(slot) == pages_before    # all exclusive → all spill
    rec = p.spill(slot)
    # the slot fully freed: pages back on the free list, slot reusable,
    # the record snapshots length/watermark/reservation exactly
    assert rec.spilled_pages == pages_before and rec.n_spilled == 3
    assert rec.slot_len == 9 and rec.committed == 6 and rec.reserved == 0
    assert p.pages_in_use == 0 and p.num_free_slots == 4
    assert p.stats().spill_records == 1 and p.stats().pages_spilled == 3
    assert p.can_restore(rec)
    slot2, fresh = p.restore(rec)
    assert len(fresh) == 3 and p.slot_pages[slot2] == fresh
    assert int(p.slot_len[slot2]) == 9 and p.slot_committed[slot2] == 6
    assert not p.spill_records
    p.extend(slot2, 12)                     # resumed decode still extends
    p.free_slot(slot2)
    assert p.pages_in_use == 0
    p.verify_invariants()


def test_spill_truncate_free_mutually_safe():
    """A spilled slot is inactive: every mutator raises BEFORE mutating,
    double spill/restore/drop raise, and the failed calls leave the
    accounting bit-identical."""
    p = _pager(page_size=4)
    slot, _ = p.alloc_slot(prompt_len=6, max_new_tokens=7)
    p.slot_committed[slot] = 6
    rec = p.spill(slot)
    snap = (list(p.free_pages), p.page_tables.copy(), p._reserved)
    for bad in (lambda: p.spill(slot),
                lambda: p.truncate(slot, 4),
                lambda: p.extend(slot, 9),
                lambda: p.commit_chunk(slot, 0, 4),
                lambda: p.free_slot(slot),
                lambda: p.peek_spill(slot)):
        with pytest.raises(PageAllocationError):
            bad()
    assert (list(p.free_pages), p._reserved) == (snap[0], snap[2])
    assert (p.page_tables == snap[1]).all()
    slot2, _ = p.restore(rec)
    for dead in (lambda: p.restore(rec), lambda: p.drop_spill(rec)):
        with pytest.raises(PageAllocationError):
            dead()
    p.free_slot(slot2)
    p.verify_invariants()


def test_spill_keeps_aliased_and_indexed_pages_resident():
    """Refcount>1 and prefix-indexed pages never leave the device: the
    record inherits the slot's refcount so sharing keeps working while
    the request is parked, and restore reattaches them in place."""
    p = _pager(num_pages=17, page_size=4)
    toks = np.arange(8, dtype=np.int32)
    s1, _ = p.alloc_slot(8, 3)              # 2 full prompt pages
    p.slot_committed[s1] = 8
    p.register_prefix(s1, toks, "ns")
    sh = p.match_prefix(toks, "ns")
    s2, _ = p.alloc_slot(8, 3, shared_pages=sh)
    assert p.shared_pages == 2
    p.extend(s2, 10)                        # one private decode page
    private = p.slot_pages[s2][-1]
    assert p.peek_spill(s2) == [private]    # aliased pages stay put
    rec = p.spill(s2)
    assert rec.layout == [("kept", sh[0]), ("kept", sh[1]),
                          ("spilled", 0)]
    # s1 still owns the shared pages (ref: s1 + parked record)
    assert all(int(p.page_ref[pg]) == 2 for pg in sh)
    p.verify_invariants()
    s2b, fresh = p.restore(rec)
    assert p.slot_pages[s2b] == [sh[0], sh[1], fresh[0]]
    assert int(p.slot_len[s2b]) == 10
    p.free_slot(s1)
    p.free_slot(s2b)
    p.unpin_prefix("ns")
    assert p.pages_in_use == 0
    p.verify_invariants()


def test_drop_spill_releases_kept_refcounts():
    p = _pager(num_pages=17, page_size=4)
    toks = np.arange(8, dtype=np.int32)
    s1, _ = p.alloc_slot(8, 3)
    p.slot_committed[s1] = 8
    p.register_prefix(s1, toks, "ns")
    s2, pg2 = p.alloc_slot(8, 3, shared_pages=p.match_prefix(toks, "ns"))
    rec = p.spill(s2)
    p.drop_spill(rec)                       # parked request cancelled
    assert all(int(p.page_ref[pg]) == 1 for pg in pg2)
    with pytest.raises(PageAllocationError):
        p.drop_spill(rec)
    with pytest.raises(PageAllocationError):
        p.restore(rec)
    p.free_slot(s1)
    assert p.pages_in_use == 0
    p.verify_invariants()


def test_restore_refused_without_capacity_and_mutates_nothing():
    p = _pager(num_pages=9, page_size=4, num_slots=3, pages_per_slot=4)
    s1, _ = p.alloc_slot(prompt_len=8, max_new_tokens=5)   # 2 drawn + 1 rsv
    p.slot_committed[s1] = 8
    rec = p.spill(s1)
    assert rec.n_spilled == 2 and rec.reserved == 1
    # soak the pool so the record's 2 pages + 1 reservation no longer fit
    s2, _ = p.alloc_slot(prompt_len=16, max_new_tokens=1)  # 4 drawn
    s3, _ = p.alloc_slot(prompt_len=12, max_new_tokens=2)  # 3 drawn + 1 rsv
    assert not p.can_restore(rec)
    snap = (list(p.free_pages), p._reserved, len(p.spill_records))
    with pytest.raises(PageAllocationError):
        p.restore(rec)
    assert (list(p.free_pages), p._reserved,
            len(p.spill_records)) == snap
    p.free_slot(s3)
    assert p.can_restore(rec)               # capacity back → restorable
    slot, _ = p.restore(rec)
    p.free_slot(slot)
    p.free_slot(s2)
    p.verify_invariants()


def test_optimistic_admission_and_free_pool_extend():
    """Optimistic mode: admission covers the prompt (plus one page of
    headroom), extend draws from the free pool, truncate does NOT
    re-credit a reservation, and a dry pool raises the pressure error."""
    p = KVPager(PagerConfig(num_pages=7, page_size=4, num_slots=2,
                            pages_per_slot=6, optimistic=True))
    # worst case 6 pages > pool, but prompt needs just 1 (+1 headroom)
    assert p.can_admit(prompt_len=4, max_new_tokens=20)
    slot, _ = p.alloc_slot(prompt_len=4, max_new_tokens=20)
    assert p.slot_reserved[slot] == 0 and p._reserved == 0
    p.slot_committed[slot] = 4
    p.extend(slot, 17)                      # 5 pages drawn from the pool
    assert p.pages_in_use == 5 and p.num_free_pages == 1
    assert p.truncate(slot, 12) == 2        # pages → free list, no reserve
    assert p.slot_reserved[slot] == 0 and p.num_free_pages == 3
    p.extend(slot, 23)                      # capacity cap: 6 pages
    with pytest.raises(PageAllocationError, match="free pool exhausted|"
                                                  "over capacity"):
        p.extend(slot, 25)
    p.free_slot(slot)
    p.verify_invariants()
    # second slot exhausts the pool mid-run → pressure error names it
    a, _ = p.alloc_slot(4, 20)
    b, _ = p.alloc_slot(4, 20)
    p.slot_committed[a] = p.slot_committed[b] = 4
    p.extend(a, 16)                         # 4 pages; pool: 6-4-1-1=0 left
    with pytest.raises(PageAllocationError, match="pressure relief"):
        p.extend(b, 9)
    p.verify_invariants()                   # partial-draw raise stays sound


def test_commit_scatter_matches_logical_order():
    """Gather(commit(dense)) reproduces the dense sequence, incl. partial
    last page."""
    page_size, n_pages, pages_per_slot = 4, 9, 2
    heads, hd, layers = 2, 3, 2
    s = 6                                      # 1 full page + 2-token partial
    rng = np.random.default_rng(0)
    k = rng.normal(size=(layers, 1, s, heads, hd)).astype(np.float32)
    v = rng.normal(size=(layers, 1, s, heads, hd)).astype(np.float32)
    cache = {"seg_0": {"kv_pool": {
        "k": jnp.zeros((layers, n_pages, heads, page_size, hd)),
        "v": jnp.zeros((layers, n_pages, heads, page_size, hd))}}}
    prefill = {"seg_0": {"kv": {"k": jnp.asarray(k), "v": jnp.asarray(v)}}}
    phys = jnp.asarray([5, 2], jnp.int32)
    out = commit_prefill(cache, prefill, jnp.int32(0), phys,
                         page_size=page_size)
    pool = out["seg_0"]["kv_pool"]["k"]
    table = np.zeros((1, pages_per_slot), np.int32)
    table[0, :2] = [5, 2]
    gathered = np.asarray(pool)[:, table[0]].swapaxes(2, 3).reshape(
        layers, -1, heads, hd)
    np.testing.assert_array_equal(gathered[:, :s], k[:, 0])
    # pages not owned by the slot stay zero
    untouched = [i for i in range(n_pages) if i not in (5, 2)]
    assert not np.asarray(pool)[:, untouched].any()
