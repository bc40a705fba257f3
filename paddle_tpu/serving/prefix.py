"""Shared-prefix KV reuse: a page-granularity radix index over the
paged pool (the vLLM/SGLang prefix-cache design on the PR-6 engine).

Every request that finishes (or is evicted) leaves its COMPLETE KV
pages behind in a trie keyed by page-size token runs: node ``(t_0..t_{ps-1})
-> (t_ps..)`` holds the physical page whose rows are the teacher-forced
K/V of exactly those tokens at exactly those positions. A later request
whose prompt walks the same token path ATTACHES those pages instead of
recomputing them — admission charges only the *novel* pages, and the
attached prefill steps disappear (the ``decode_prefix_hit`` bench row
measures warm vs. cold TTFT).

Correctness leans on two invariants:

- KV is a pure function of (token run, positions): pages are only
  inserted for fully teacher-forced token runs starting at position 0,
  so an attached page is bit-identical to what the slot would have
  written itself — greedy token-identity is preserved by construction
  (pinned in tests/test_paged_decode.py).
- Attached pages are never written: a slot admitted with ``matched``
  tokens starts scattering at position ``matched``, which lands in its
  first PRIVATE page. Divergence INSIDE a page is handled by
  copy-on-write: the shared page is device-copied into a fresh page
  (PagedDecoder.copy_page — one compile, traced src/dst) and the match
  extends to the common rows of the copy.

Ownership is reference counting in :class:`~paddle_tpu.serving.engine.PagePool`:
the trie holds ONE ref per indexed page, every slot using it holds
another; ``free()`` only returns a page to the free list at refcount
zero, and ``page_accounting()`` extends the zero-leak invariant to
``refs_total == held_by_slots + held_by_trie`` (the chaos suite's
zero-underflow assertion — tests/test_serving_faults.py family (n)).

Under pool pressure the engine reclaims least-recently-used LEAF nodes
(refcount 1 — trie-only) BEFORE preempting a running slot, journaled as
``engine/prefix_evict``. All trie state is guarded by the named
``serving.prefix`` InstrumentedLock (analysis/lockdep.py): mutation
happens on the engine's stepping thread, but stats()/flight providers
read from arbitrary threads. Lock order is engine -> prefix -> pagepool
(never the reverse), witnessed by the autouse lockdep fixture.

STATE SNAPSHOTS. A cache kind that keeps a recurrent state a slot beside
its pages (models/block.py ``StateLatentCache``) cannot start a slot at
position n from pages alone: it needs the state after exactly n tokens.
A node may therefore own a SNAPSHOT: a row of the kind's state pool that
holds the state of all layers after the tokens of the path that ends
with this node's page (n a page boundary by construction). The index
keeps the free snapshot rows; a row returns to them when its node is
dropped (evicted with the trie's leaves, flushed) or when the row is
taken for a newer snapshot (:meth:`take_snapshot_row`): first from the
snapshots no match has used yet, least recently used first, then from
those one has (what a turn leaves behind at its prompt's end is used
again only if the conversation goes on; the history every turn of a
client starts from is used every time, but last TOUCHED when its running
turn was admitted, which under plain LRU made it the first to go:
56 % of the matched tokens were fed again, my chip run, PR 39). A match is worth only as deep as its
deepest snapshot (:meth:`PrefixMatch.cut_to_snapshot`). What is IN a row
is the engine's: the index never touches the device.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from paddle_tpu.analysis.lockdep import named_lock

__all__ = ["PrefixIndex", "PrefixMatch"]


class _Node:
    __slots__ = ("key", "page", "parent", "children", "last_used",
                 "snapshot", "snapshot_used")

    def __init__(self, key, page, parent):
        self.key = key                   # page_size token tuple
        self.page = page                 # physical page id (None: root)
        self.parent = parent
        self.children: Dict[tuple, "_Node"] = {}
        self.last_used = 0
        self.snapshot: Optional[int] = None   # a state-pool row, or none
        self.snapshot_used = False            # a match ended on it


class PrefixMatch:
    """One lookup's result: ``pages`` are fully-shared physical pages
    (in logical order), ``matched`` counts their tokens, and ``cow``
    (when set) is ``(physical_page, rows)`` — the best partially-
    matching child page whose first ``rows`` tokens agree, a
    copy-on-write candidate. ``snapshots`` runs beside ``pages``: the
    state-pool row that holds the state after each page's last token,
    or None."""

    __slots__ = ("pages", "matched", "cow", "snapshots")

    def __init__(self, pages: List[int], matched: int,
                 cow: Optional[Tuple[int, int]],
                 snapshots: Optional[List[Optional[int]]] = None):
        self.pages = pages
        self.matched = matched
        self.cow = cow
        self.snapshots = snapshots or [None] * len(pages)

    def cut_to_snapshot(self, page_size: int) -> "PrefixMatch":
        """The match as deep as its deepest snapshot and no deeper (no
        copy-on-write: rows of a page are no use without the state at
        them); no snapshot on the path is no match."""
        deep = max((j + 1 for j, row in enumerate(self.snapshots)
                    if row is not None), default=0)
        return PrefixMatch(self.pages[:deep], deep * page_size, None,
                           self.snapshots[:deep])


class PrefixIndex:
    """Radix/trie index of shared KV pages (see module doc). All
    public methods take the named ``serving.prefix`` lock; the engine
    calls the mutators from its stepping thread only."""

    def __init__(self, pool, page_size: int, snapshot_rows=()):
        self.pool = pool
        self.page_size = int(page_size)
        #: the state pool's snapshot rows, and those no node owns
        # leaves an eviction may take, coldest LAST, as one walk found
        # them: [(last_used then, node)]. Each eviction used to walk the
        # whole trie for its one victim: with every page of a 12,288-page
        # pool held by the trie that was 10 ms of host time a step (my
        # chip run, PR 39); now a walk serves evictions until it runs dry
        self._cold: list = []          # ptlint: guarded-by(serving.prefix)
        self.snapshot_rows = tuple(snapshot_rows)
        self._snapshot_free = list(reversed(self.snapshot_rows))  # ptlint: guarded-by(serving.prefix)
        self.snapshots_dropped = 0
        self._lock = named_lock("serving.prefix")
        # the radix trie + LRU clock  # ptlint: guarded-by(serving.prefix)
        self._root = _Node(None, None, None)
        self._seq = 0                  # ptlint: guarded-by(serving.prefix)
        self._nodes = 0                # ptlint: guarded-by(serving.prefix)
        self.hit_pages = 0
        self.miss_pages = 0
        self.cow_hits = 0
        self.inserted_pages = 0
        self.evicted_pages = 0

    # --------------------------------------------------------------- lookup
    def match(self, tokens) -> PrefixMatch:
        """Longest shared-page walk for ``tokens`` (prompt + replayed
        generation). The match is capped at ``len(tokens) - 1`` so the
        slot always has at least one token left to feed — the step
        needs a query to produce the next token."""
        ps = self.page_size
        toks = [int(t) for t in tokens]
        limit = len(toks) - 1
        pages: List[int] = []
        snaps: List[Optional[int]] = []
        cow = None
        with self._lock:
            node = self._root
            deepest = None
            i = 0
            while i + ps <= limit:
                child = node.children.get(tuple(toks[i:i + ps]))
                if child is None:
                    break
                self._seq += 1
                child.last_used = self._seq
                pages.append(child.page)
                snaps.append(child.snapshot)
                if child.snapshot is not None:
                    deepest = child
                node = child
                i += ps
            if deepest is not None:     # the one a state kind starts from
                deepest.snapshot_used = True
            # partial-page (copy-on-write) candidate: the child sharing
            # the longest leading token run inside the next page
            best = 0
            best_child = None
            remaining = toks[i:]
            for key, child in node.children.items():
                j = 0
                cap = min(ps, limit - i)
                while j < cap and key[j] == remaining[j]:
                    j += 1
                if j > best:
                    best = j
                    best_child = child
                    cow = (child.page, j)
            if best_child is not None:
                self._seq += 1
                best_child.last_used = self._seq
        return PrefixMatch(pages, i, cow, snaps)

    # -------------------------------------------------------------- insert
    def insert(self, tokens, pages: List[int]) -> int:
        """Register the COMPLETE pages of a finished/evicted slot's
        teacher-forced token run. ``pages`` is the slot's physical page
        list (logical order). Existing nodes are touched, novel ones
        take one pool ref each. Returns the number of new nodes."""
        ps = self.page_size
        toks = [int(t) for t in tokens]
        new = 0
        with self._lock:
            node = self._root
            i = 0
            while i + ps <= len(toks) and i // ps < len(pages):
                key = tuple(toks[i:i + ps])
                child = node.children.get(key)
                if child is None:
                    page = pages[i // ps]
                    self.pool.ref(page)
                    self.pool.index(page)
                    child = _Node(key, page, node)
                    node.children[key] = child
                    self._nodes += 1
                    new += 1
                self._seq += 1
                child.last_used = self._seq
                node = child
                i += ps
            self.inserted_pages += new
        return new

    # ------------------------------------------------------------ eviction
    def evict_lru(self, n: int = 1) -> List[int]:
        """Free up to ``n`` least-recently-used LEAF pages whose only
        owner is the trie (pool refcount 1). Returns the freed physical
        pages — inner nodes become leaves as their children go, so a
        caller looping this reclaims whole cold branches."""
        freed: List[int] = []
        with self._lock:
            while len(freed) < n:
                victim = self._coldest_locked()
                if victim is None:
                    break
                parent = victim.parent
                self._drop_locked(victim)
                freed.append(victim.page)
                if parent.page is not None and not parent.children and \
                        self.pool.refcount(parent.page) == 1:
                    # a leaf now: among the cold ones by its own age
                    bisect.insort(self._cold, (parent.last_used, parent),
                                  key=lambda e: -e[0])
            self.evicted_pages += len(freed)
        return freed

    def _coldest_locked(self) -> Optional[_Node]:
        """The least recently used leaf only the trie holds, or None. It
        comes off the list the last walk left; an entry that has since
        been touched, taken by a slot, given children or dropped is
        passed over (it is found again, at its new age, by the next
        walk); a walk is made when the list runs dry. A page that only
        became cold after the walk was touched after it, so everything
        on the list is older."""
        for walked in (False, True):
            while self._cold:
                stamp, nd = self._cold.pop()
                if nd.last_used == stamp and not nd.children and \
                        nd.parent.children.get(nd.key) is nd and \
                        self.pool.refcount(nd.page) == 1:
                    return nd
            if walked:
                return None
            refs = self.pool.refcounts()
            stack = [self._root]
            while stack:
                nd = stack.pop()
                if nd.page is not None and not nd.children and \
                        refs.get(nd.page) == 1:
                    self._cold.append((nd.last_used, nd))
                stack.extend(nd.children.values())
            self._cold.sort(key=lambda e: -e[0])
        return None

    def _drop_locked(self, node: _Node) -> None:
        """Unlink a leaf and give its page's ref back to the pool, its
        snapshot row back to the free rows (the caller holds the lock)."""
        del node.parent.children[node.key]
        self._nodes -= 1
        self._free_snapshot_locked(node)
        self.pool.unindex(node.page)
        self.pool.free([node.page])

    # ----------------------------------------------------------- snapshots
    def _free_snapshot_locked(self, node: _Node) -> None:
        if node.snapshot is not None:
            self._snapshot_free.append(node.snapshot)
            node.snapshot, node.snapshot_used = None, False
            self.snapshots_dropped += 1

    def _node_at_locked(self, tokens) -> Optional[_Node]:
        """The node of the page that ends at ``len(tokens)`` (a whole
        number of pages) on that token path, or None."""
        ps = self.page_size
        toks = [int(t) for t in tokens]
        if not toks or len(toks) % ps:
            return None
        node = self._root
        for i in range(0, len(toks), ps):
            node = node.children.get(tuple(toks[i:i + ps]))
            if node is None:
                return None
        return node

    def has_snapshot(self, tokens) -> Optional[bool]:
        """Does the node at ``tokens`` own a snapshot (None: no such
        node). A node that does is touched."""
        with self._lock:
            node = self._node_at_locked(tokens)
            if node is None:
                return None
            if node.snapshot is not None:
                self._seq += 1
                node.last_used = self._seq
            return node.snapshot is not None

    def take_snapshot_row(self) -> Optional[int]:
        """A state-pool row for a new snapshot: a free one, else a
        node's (whose pages stay: a match through it is cut back from now
        on): the least recently used of those no match has ended on, or
        failing those of all. None where the kind keeps none."""
        with self._lock:
            if self._snapshot_free:
                return self._snapshot_free.pop()
            victim = None
            stack = [self._root]
            while stack:
                nd = stack.pop()
                if nd.snapshot is not None and (
                        victim is None
                        or (nd.snapshot_used, nd.last_used)
                        < (victim.snapshot_used, victim.last_used)):
                    victim = nd
                stack.extend(nd.children.values())
            if victim is None:
                return None
            self._free_snapshot_locked(victim)
            return self._snapshot_free.pop()

    def set_snapshot(self, tokens, row: int) -> bool:
        """The node at ``tokens`` owns ``row`` from now on (the engine
        has copied the state there). False, and the row free again, where
        the node is gone or has one."""
        with self._lock:
            node = self._node_at_locked(tokens)
            if node is None or node.snapshot is not None:
                self._snapshot_free.append(row)
                return False
            node.snapshot = row
            return True

    def free_snapshot_row(self, row: int) -> None:
        """A row from :meth:`take_snapshot_row` that no node came to own."""
        with self._lock:
            self._snapshot_free.append(row)

    def snapshot_accounting(self) -> dict:
        """Rows by where they are; ``leaked`` is what neither the free
        rows nor a node holds (0 always)."""
        with self._lock:
            held, stack = 0, [self._root]
            while stack:
                nd = stack.pop()
                held += nd.snapshot is not None
                stack.extend(nd.children.values())
            total, free = len(self.snapshot_rows), len(self._snapshot_free)
        return {"snapshot_rows_total": total, "snapshot_rows_free": free,
                "snapshot_rows_held": held,
                "snapshot_rows_leaked": total - free - held}

    # --------------------------------------------------------------- spill
    @staticmethod
    def _path_of(node: _Node) -> tuple:
        """Full token path from the root through ``node`` — the spill
        store's key (serving/spill.py): restores look the SAME token
        run back up, so the key must be reconstructable from the
        request's replay alone."""
        keys = []
        while node.key is not None:
            keys.append(node.key)
            node = node.parent
        out = []
        for key in reversed(keys):
            out.extend(key)
        return tuple(out)

    def spill_candidates(self, n: int = 1) -> List[Tuple[tuple, int]]:
        """Up to ``n`` least-recently-used LEAF pages whose only owner
        is the trie, as ``(token_path, physical_page)`` — NO mutation.
        The engine spills these device->host and then calls
        :meth:`evict_exact` per page, keeping the crash-safety
        ordering (read, evict+free, commit) under ITS control."""
        with self._lock:
            leaves = []
            refs = self.pool.refcounts()
            stack = [self._root]
            while stack:
                nd = stack.pop()
                if nd.page is not None and not nd.children and \
                        refs.get(nd.page) == 1:
                    leaves.append(nd)
                stack.extend(nd.children.values())
            leaves.sort(key=lambda nd: nd.last_used)
            return [(self._path_of(nd), nd.page) for nd in leaves[:n]]

    def evict_exact(self, path: tuple) -> Optional[int]:
        """Remove the node at exactly ``path`` (a full token path) and
        free its page — the evict+free step of the spill ordering. The
        node must still be a trie-only (refcount 1) childless leaf;
        returns the freed page, or None if the node changed since
        :meth:`spill_candidates` picked it (grew children, gained a
        slot ref, vanished) — the caller then simply skips the spill."""
        with self._lock:
            node = self._node_at_locked(path)
            if node is None or node.children or \
                    self.pool.refcount(node.page) != 1:
                return None
            self._drop_locked(node)
            self.evicted_pages += 1
            return node.page

    def reclaimable_pages(self) -> int:
        """Pages an eviction loop could eventually return to the free
        list: trie pages no slot is also holding (refcount 1)."""
        return self.pool.reclaimable

    # ------------------------------------------------------------ lifecycle
    def flush(self) -> int:
        """Drop the whole index, returning every trie ref to the pool
        (shared pages stay allocated for the slots still holding them).
        Returns the number of dropped nodes."""
        with self._lock:
            dropped = self._collect_pages()
            for page in dropped:
                self.pool.unindex(page)
                self.pool.free([page])
            n = self._nodes
            self._root = _Node(None, None, None)
            self._nodes = 0
            self._cold = []
            self._snapshot_free = list(reversed(self.snapshot_rows))
        return n

    def reset(self) -> None:
        """Forget every node WITHOUT touching the pool — the step-
        failure recovery path, where the engine has already rebuilt the
        PagePool from scratch."""
        with self._lock:
            self._root = _Node(None, None, None)
            self._nodes = 0
            self._cold = []
            self._snapshot_free = list(reversed(self.snapshot_rows))

    def _collect_pages(self) -> List[int]:
        pages = []
        stack = [self._root]
        while stack:
            nd = stack.pop()
            if nd.page is not None:
                pages.append(nd.page)
            stack.extend(nd.children.values())
        return pages

    # ------------------------------------------------------------ snapshots
    def page_count(self) -> int:
        with self._lock:
            return self._nodes

    def summary(self) -> dict:
        """Flight-bundle / stats() view: trie shape + cumulative hit
        accounting + the pool's refcount histogram."""
        with self._lock:
            depth = 0
            stack = [(self._root, 0)]
            while stack:
                nd, d = stack.pop()
                depth = max(depth, d)
                stack.extend((c, d + 1) for c in nd.children.values())
            return {
                "nodes": self._nodes,
                "pages": self._nodes,
                "max_depth_pages": depth,
                "hit_pages": self.hit_pages,
                "miss_pages": self.miss_pages,
                "cow_copies": self.cow_hits,
                "inserted_pages": self.inserted_pages,
                "evicted_pages": self.evicted_pages,
                "refcount_histogram": self.pool.refcount_histogram(),
            }
