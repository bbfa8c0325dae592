"""Host-side page accounting — the jax-free half of the paged KV cache.

Split out of paged_cache.py (ISSUE 10): the scheduler/prefix-cache
policy layer is declared jax-free (`mctpu lint` MCT001 — it must run in
the fleet's sim storms and offline tools without pulling jax), but its
page-accounting primitive used to live next to the device-side
pools/kernels, so importing PagePool imported jax transitively. The
accounting is pure host bookkeeping; it moves here, and paged_cache
re-exports it so device-side callers keep one import surface.
"""

from __future__ import annotations


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold `tokens` cache entries (ceil)."""
    return -(-tokens // page_size)


class PagePool:
    """Host-side page accounting: which physical page belongs to which
    owner. Page 0 is the reserved scratch page and is never issued.

    The pool is the safety layer under the scheduler: alloc hands out
    each page exactly once, free verifies ownership (a double free or a
    free of someone else's page raises instead of silently corrupting a
    neighbor sequence), and `check()` asserts the global invariant
    free + allocated == usable after any admit/finish/preempt sequence
    (tests/test_serve.py drives it through all three).

    Prefix sharing (ISSUE 9) adds REFCOUNTED READ-ONLY pages on top of
    the exclusive-owner model: `adopt(..., readonly=True)` transfers a
    full prompt page to the prefix cache and freezes it, `share`/
    `unshare` grant and return per-reader references, and `free`
    refuses any page with live readers. `check()` now also proves
    refcount conservation (every reader entry sits on an owned,
    read-only page, no duplicate grants) and that no writable page is
    ever shared — the copy-on-write safety story in one invariant.

    `check_changed()` proves the same invariant at the cost of what
    changed since the last check of either form: every mutator enters
    the pages it changes in a journal (page -> its owner at the last
    check, None where it was free), which the next check drains.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"num_pages {num_pages} < 2 (page 0 is scratch)")
        self.num_pages = num_pages
        # Pop from the end -> pages issue in ascending order
        # (deterministic layouts for tests and debugging).
        self._free = list(range(num_pages - 1, 0, -1))
        self._free_set = set(self._free)      # the same pages, for `in`
        self._owner: dict[int, object] = {}
        self._readers: dict[int, list] = {}   # page -> live reader refs
        self._ro: set[int] = set()            # read-only (shareable) pages
        self._touched: dict[int, object] = {}  # the journal, undrained

    @property
    def usable(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def owned_by(self, owner) -> list[int]:
        return [p for p, o in self._owner.items() if o == owner]

    def try_alloc(self, n: int, owner) -> list[int] | None:
        """n pages for `owner`, or None (and no change) if the pool
        cannot cover the request — admission control's primitive."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        for p in pages:
            self._owner[p] = owner
            self._touched.setdefault(p, None)
        return pages

    def free(self, pages: list[int], owner) -> None:
        for p in pages:
            got = self._owner.get(p)
            if got is None:
                raise RuntimeError(f"double free of page {p} (owner {owner})")
            if got != owner:
                raise RuntimeError(
                    f"page {p} is owned by {got}, not {owner} — refusing "
                    "to free another sequence's page"
                )
            if self._readers.get(p):
                raise RuntimeError(
                    f"page {p} still has {len(self._readers[p])} live "
                    f"reader(s) — refusing to free a shared page"
                )
        for p in pages:
            self._touched.setdefault(p, self._owner.pop(p))
            self._ro.discard(p)
            self._free.append(p)
            self._free_set.add(p)

    # -- refcounted sharing (ISSUE 9) -----------------------------------

    def adopt(self, page: int, old_owner, new_owner, *,
              readonly: bool = False) -> None:
        """Transfer one page's ownership (slot -> prefix cache at
        insert time). readonly=True freezes it: from here on it can be
        shared but never written or handed to a writer again."""
        got = self._owner.get(page)
        if got != old_owner:
            raise RuntimeError(
                f"page {page} is owned by {got}, not {old_owner} — "
                "refusing the ownership transfer"
            )
        self._touched.setdefault(page, got)
        self._owner[page] = new_owner
        if readonly:
            self._ro.add(page)

    def freeze(self, page: int, owner) -> None:
        """Mark an owned page read-only WITHOUT an ownership transfer —
        the host-tier readmission primitive (ISSUE 17): the prefix
        cache allocates a fresh device page under its own owner and
        freezes it before restoring spilled content, so the page enters
        the shareable set under the same no-writable-page-shared
        invariant adopt(readonly=True) provides at insert time."""
        got = self._owner.get(page)
        if got != owner:
            raise RuntimeError(
                f"page {page} is owned by {got}, not {owner} — "
                "refusing to freeze it"
            )
        self._touched.setdefault(page, got)
        self._ro.add(page)

    def share(self, page: int, reader) -> None:
        """Grant `reader` one reference on a read-only page. Sharing a
        writable page is the corruption this layer exists to prevent —
        it raises."""
        if page not in self._owner:
            raise RuntimeError(f"cannot share unowned page {page}")
        if page not in self._ro:
            raise RuntimeError(
                f"page {page} is writable — refusing to share it "
                "(adopt it read-only first)"
            )
        rl = self._readers.setdefault(page, [])
        if reader in rl:
            raise RuntimeError(
                f"reader {reader} already holds a reference on page {page}"
            )
        self._touched.setdefault(page, self._owner[page])
        rl.append(reader)

    def unshare(self, page: int, reader) -> None:
        """Return `reader`'s reference on a shared page (ownership-
        checked like free: a foreign or double unshare raises)."""
        rl = self._readers.get(page)
        if rl is None or reader not in rl:
            raise RuntimeError(
                f"reader {reader} holds no reference on page {page}"
            )
        self._touched.setdefault(page, self._owner.get(page))
        rl.remove(reader)
        if not rl:
            del self._readers[page]

    def refs(self, page: int) -> int:
        return len(self._readers.get(page, ()))

    def is_shared(self, page: int) -> bool:
        return page in self._ro

    def _check_counts(self) -> None:
        """What both forms of the check verify whole: the counts, and
        the scratch page kept out of circulation."""
        assert len(self._free) + len(self._owner) == self.usable, (
            f"page leak: {len(self._free)} free + {len(self._owner)} "
            f"owned != {self.usable} usable"
        )
        assert 0 not in self._owner and 0 not in self._free_set, (
            "scratch page 0 entered circulation"
        )

    def _check_readers(self, p: int, rl: list) -> None:
        # Refcount conservation: every reader entry sits on an owned
        # page, lists are non-empty (emptied lists are deleted), and no
        # reader holds two references on one page.
        assert p in self._owner, f"readers on unowned page {p}"
        assert rl, f"empty reader list retained for page {p}"
        assert len(rl) == len({id(r) if isinstance(r, (list, dict))
                               else r for r in rl}), (
            f"duplicate reader reference on page {p}"
        )

    def check(self) -> None:
        """The no-leak / no-double-book invariant, extended with
        refcount conservation and the no-writable-shared-page
        guarantee; over every page. Drains the journal."""
        self._check_counts()
        assert set(self._free) == self._free_set, "free list and set differ"
        assert not (self._free_set & set(self._owner)), "page double-booked"
        for p, rl in self._readers.items():
            self._check_readers(p, rl)
        # No writable page is ever shared; read-only pages are owned.
        assert set(self._readers) <= self._ro, "writable page shared"
        assert self._ro <= set(self._owner), "read-only page not owned"
        self._touched = {}

    def check_changed(self) -> dict[int, object]:
        """`check` over the pages changed since the last check, and the
        counts: a page the mutators did not touch is as the last check
        proved it. Returns the drained journal (page -> its owner at the
        last check), for the slots' checks above the pool."""
        self._check_counts()
        assert len(self._free_set) == len(self._free), "page double-booked"
        for p in self._touched:
            owned = p in self._owner
            assert owned != (p in self._free_set), (
                f"page {p} double-booked" if owned
                else f"page leak: page {p} neither free nor owned")
            rl = self._readers.get(p)
            if rl is not None:
                self._check_readers(p, rl)
                assert p in self._ro, "writable page shared"
            if p in self._ro:
                assert owned, "read-only page not owned"
        touched, self._touched = self._touched, {}
        return touched


def window_pages_per_slot(window: int, chunk: int, page_size: int,
                          max_len: int) -> int:
    """The most pages one slot can hold in a windowed layer group: the
    window's rows and one forward's new rows, plus a page for where
    they lie in their pages; never more than a whole sequence."""
    return min(pages_for(window + chunk, page_size) + 1,
               pages_for(max_len, page_size))


class WindowGroup:
    """The page accounting of a model's WINDOWED layer group (layers
    that see the last `window` keys only, beside global ones that see
    all): its own PagePool and, a slot, its own block table
    (`Slot.wpages`: logical block -> page, 0 where the slot holds
    none). The global group is the scheduler's pool, unchanged; this
    group follows it.

    A slot holds here only what its next forward can read or write: it
    TAKES a page when its rows grow into one and GIVES BACK every page
    that lies wholly behind its window (`advance`, called before each
    prefill chunk and each decode tick: rows [cached, cached + n) are
    written, keys from cached - window + 1 on are read). So a slot
    never holds more than `per_slot` = pages_for(window + chunk) + 1
    pages whatever its depth, and the pool is sized to slots x that:
    it never runs dry, and admission, growth and preemption stay the
    global pool's to decide (the paged draft pool's precedent,
    engine.PagedDraftProposer). What a slot holds is a function of its
    `cached` alone, so the per-tick state digest, which has `cached`,
    pins it too. A windowed pool under full coverage, with a dry path
    of its own, is ROADMAP R5's remainder."""

    def __init__(self, *, window: int, chunk: int, page_size: int,
                 slots: int, max_len: int):
        if window < 1 or chunk < 1:
            raise ValueError(f"window {window}, chunk {chunk}: want >= 1")
        self.window, self.chunk, self.page_size = window, chunk, page_size
        self.per_slot = window_pages_per_slot(window, chunk, page_size,
                                              max_len)
        self.pool = PagePool(slots * self.per_slot + 1)
        self._freed = 0     # pages given back behind a window, undrained
        # The pages each slot's table held when a check last walked it:
        # their sum is what the tables hold, as the pool must have issued.
        self._held = [0] * slots

    def advance(self, slot, rows: int) -> None:
        """Before `slot` writes `rows` rows from `slot.cached`: give
        back the pages wholly behind the window of its first new row,
        take the pages the new rows grow into."""
        ps, table, owner = self.page_size, slot.wpages, slot.req.rid
        keep = max(slot.cached - self.window + 1, 0) // ps
        behind = [p for p in table[slot.wfirst:keep] if p]
        if behind:
            self.pool.free(behind, owner)
            self._freed += len(behind)
            table[slot.wfirst:keep] = [0] * len(table[slot.wfirst:keep])
        slot.wfirst = max(slot.wfirst, min(keep, len(table)))
        want = pages_for(slot.cached + rows, ps)
        if want > len(table):
            fresh = max(len(table), keep)
            got = self.pool.try_alloc(want - fresh, owner)
            assert got is not None, "window pool sized to full coverage"
            table.extend([0] * (fresh - len(table)) + got)

    def drain_freed(self) -> int:
        """Pages given back behind a window since the last call (the
        tick record's `window_pages_freed`)."""
        out, self._freed = self._freed, 0
        return out

    def release(self, slot) -> None:
        """Every page `slot` holds here, back to the pool (its request
        ended or was preempted)."""
        pages = [p for p in slot.wpages[slot.wfirst:] if p]
        if pages:
            self.pool.free(pages, slot.req.rid)
        slot.wpages, slot.wfirst = [], 0

    def check_slot(self, s) -> None:
        """That `s`'s table and the pool agree page for page, within the
        bound a slot; counts the pages it holds toward `check_held`."""
        if s.free:
            assert not s.wpages, "a free slot holds windowed pages"
            self._held[s.idx] = 0
            return
        owner_of = self.pool._owner
        mine = [p for p in s.wpages[s.wfirst:] if p]
        assert not any(s.wpages[:s.wfirst]), "a page behind wfirst"
        assert all(owner_of.get(p) == s.req.rid for p in mine), (
            f"slot {s.idx}'s windowed table names a page it does "
            "not own")
        assert len(mine) <= self.per_slot, (
            f"slot {s.idx} holds {len(mine)} windowed pages, over "
            f"the bound {self.per_slot}")
        self._held[s.idx] = len(mine)

    def check_held(self) -> None:
        """The tables hold what the pool has issued, by the counts of
        each slot's last walk (`check_slot`)."""
        held = sum(self._held)
        assert held == len(self.pool._owner), (
            f"the slots' windowed tables hold {held} pages, the pool "
            f"has issued {len(self.pool._owner)}")

    def check_changed(self) -> tuple[int, set]:
        """The pool's `check_changed`. Returns the pages verified and
        the owners, before and after, of those that changed: the slots
        whose tables the caller walks again (`check_slot`), beside those
        that changed, before `check_held`."""
        touched = self.pool.check_changed()
        owner_of = self.pool._owner
        return len(touched), {*touched.values(),
                              *(owner_of.get(p) for p in touched)}

    def check(self, slots) -> None:
        """The pool's invariant, and that the slots' tables and the
        pool agree page for page, within the bound a slot."""
        self.pool.check()
        for s in slots:
            self.check_slot(s)
        self.check_held()
