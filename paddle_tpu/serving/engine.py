"""Continuous-batching decode engine over a paged KV cache.

The dense-cache decoder (models/decode.py) serves generation the way
2017 served everything: per-request-batch cache allocation, whole-batch
lockstep, one compilation per shape. Under ragged production traffic
that wastes the chip twice — short sequences pad to the longest, and a
finished sequence's slot idles until the whole batch drains. This
module is the production loop those papers (Orca's iteration-level
scheduling, PagedAttention's block-pooled KV) built for serving LLMs:

- ``PagePool``: a host-side REFCOUNTED free-list over preallocated
  device page pools ([L, n_pages, page_size, g*dh], the layout the
  paged kernel reads, updated in place — models/decode.PagedDecoder;
  the engine holds them as opaque pytrees). KV memory is pooled across ALL requests
  in fixed-size pages, so admission is a pages-free check, not a
  worst-case-length reservation. A page may be owned by several slots
  AND the prefix trie at once; it returns to the free list only at
  refcount zero.
- ``DecodeEngine``: a persistent decode loop over a FIXED slot batch.
  Each iteration feeds every active slot a WINDOW of up to W tokens and,
  through the step's PREFILL LANES, the oldest prompts a chunk each
  (a prompt enters a lane's width of tokens at a time beside the
  decoding slots, in the one dispatch that reads the weights anyway:
  time to first token follows steps, not prompt tokens — prefill
  interleaves with other slots' decoding, no whole-batch barrier),
  dispatches ONE jitted step, and does host-side bookkeeping: requests
  join free slots mid-flight,
  finished/cancelled/expired requests free their pages immediately, and
  page-pool exhaustion first reclaims cold prefix-cache pages, then
  PREEMPTS the youngest request (pages back to the pool, request
  re-queued; greedy decode replays prompt + generated tokens, so its
  final output is unchanged). Joins/evictions only edit small int32
  inputs — the step never recompiles.
- Admission control by FREE KV PAGES: a request that could never fit
  the pool is rejected outright (``kv_capacity``); the queue head only
  takes a slot when enough NOVEL pages are free to reach its first new
  token (shared-prefix pages are free to attach); the wait queue
  itself is bounded (``queue_full``).

Round 9 stacks the three decode-speed multipliers on that loop:

- **Shared-prefix KV reuse** (serving/prefix.py): finished/evicted
  slots leave their complete pages in a radix index; a new request
  whose prompt walks the same token path attaches those pages instead
  of recomputing them — admission charges only novel pages, warm-
  prefix TTFT drops the whole shared prefill, and divergence inside a
  page is copy-on-write via ``PagedDecoder.copy_page``.
- **Speculative decoding** (models/decode.DraftDecoder): a small draft
  proposes up to k tokens per slot; the target VERIFIES them in the
  same [S, W] jitted step it uses for prefill (W = spec_k + 1 fixed at
  construction — zero new compiles under churn). Greedy token-identity
  is the acceptance rule, so output is token-exact vs. the dense
  baseline; rejected rows are dead weight the kv_len mask never reads
  and the next feed overwrites.
- **Allocated-pages attention** (ops/pallas_decode.py): the paged step
  walks only each slot's allocated pages on the TPU kernel path,
  cutting cache reads from ``max_seq_len`` to true ragged lengths.

``stats()`` exports KV-page occupancy, slot utilization, per-token
latency percentiles, prefix-hit and speculation accounting and the
scheduling counters; serving/http.py re-exports them as Prometheus
gauges on GET /metrics, alongside the module-level
``paddle_tpu_prefix_*`` / ``paddle_tpu_spec_*`` families registered
here. Faults for the chaos suite (mid-decode join/evict/cancel, CoW
churn, cancel-mid-verify) drive the ``_step_interceptor`` seam — see
testing/faults.py (j)+(n) and tests/test_serving_faults.py.
docs/perf.md ("Continuous batching", "Prefix reuse + speculative
decoding") has the measured before/after; docs/robustness.md the
fault families.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from paddle_tpu.obs import context as obs_context
from paddle_tpu.analysis.lockdep import named_condition, named_lock
from paddle_tpu.obs.events import emit as journal_emit
from paddle_tpu.obs.flight import FLIGHT
from paddle_tpu.obs.metrics import REGISTRY as _METRICS
from paddle_tpu.obs.profile import PROFILER
from paddle_tpu.serving.prefix import PrefixIndex
from paddle_tpu.serving.server import (Expired, Rejected, ServerClosed,
                                       ServingError)
from paddle_tpu.serving.spill import SpillEntry, SpillStore
from paddle_tpu.utils.stats import (GC_WATCH, global_counters, stat_timer,
                                    watch_gc)

# /metrics families for the round-9 multipliers (idempotent: the
# registry returns the existing family on re-registration)
_PREFIX_HIT = _METRICS.counter(
    "paddle_tpu_prefix_hit_pages",
    "KV pages attached from the shared-prefix index instead of "
    "recomputed")
_PREFIX_MISS = _METRICS.counter(
    "paddle_tpu_prefix_miss_pages",
    "prompt pages admitted with no shared-prefix match")
_PREFIX_COW = _METRICS.counter(
    "paddle_tpu_prefix_cow_copies",
    "copy-on-write page copies on intra-page prefix divergence")
_PREFIX_SHARED = _METRICS.gauge(
    "paddle_tpu_prefix_shared_pages",
    "physical pages currently referenced by more than one owner")
_SPEC_PROPOSED = _METRICS.counter(
    "paddle_tpu_spec_proposed_tokens_total",
    "draft-model tokens proposed for target verification")
_SPEC_ACCEPTED = _METRICS.counter(
    "paddle_tpu_spec_accepted_tokens_total",
    "draft proposals the target model accepted (greedy token match)")
# the two-tier KV plane (int8 pages + host spill — docs/robustness.md
# "Two-tier KV cache")
_SPILL_PAGES = _METRICS.counter(
    "paddle_tpu_kv_pages_spilled_total",
    "cold prefix-cache pages spilled device->host instead of freed")
_SPILL_RESTORED = _METRICS.counter(
    "paddle_tpu_kv_pages_restored_total",
    "spilled pages restored host->device on a prefix match, before "
    "prefill was charged")
_SPILL_INTEGRITY = _METRICS.counter(
    "paddle_tpu_kv_spill_integrity_drops_total",
    "spill entries dropped on checksum mismatch or transfer failure "
    "— a torn page degrades to a prefix miss, never a restore")
_SPILLED_NOW = _METRICS.gauge(
    "paddle_tpu_kv_pages_spilled_now",
    "pages currently resident in the host-RAM spill tier")


class PagePool:
    """Host-side refcounted allocator over the device page pools.

    Physical page 0 is RESERVED as the null page (inactive slots write
    there; unassigned page-table entries point there) and is never
    handed out. ``alloc()`` hands a page out at refcount 1; the prefix
    trie and additional slots take further refs with ``ref()``;
    ``free()`` decrements and only returns the page to the free list
    at zero. Freeing a page that holds no refs raises — refcount
    UNDERFLOWS are as loud as double frees, and the chaos suite
    asserts ``leaked == 0`` after every fault storm."""

    def __init__(self, num_pages: int):
        assert num_pages >= 2, num_pages
        self.num_pages = int(num_pages)
        self.usable = self.num_pages - 1
        self._lock = named_lock("serving.pagepool")
        # pop() hands out page 1 first — deterministic layouts in tests
        self._free_list = list(range(self.num_pages - 1, 0, -1))
        self._allocated: set = set()     # ptlint: guarded-by(serving.pagepool)
        self._refs: Dict[int, int] = {}  # ptlint: guarded-by(serving.pagepool)
        # pages the prefix index holds a ref on, and how many of them
        # have no other holder: kept as refcounts change, so that an
        # admission reads it where it used to walk the whole trie
        self._indexed: set = set()       # ptlint: guarded-by(serving.pagepool)
        self._reclaimable = 0            # ptlint: guarded-by(serving.pagepool)
        self.high_water = 0

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free_list)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return len(self._allocated)

    @property
    def shared_pages(self) -> int:
        with self._lock:
            return sum(1 for c in self._refs.values() if c > 1)

    def alloc(self) -> Optional[int]:
        with self._lock:
            if not self._free_list:
                return None
            p = self._free_list.pop()
            self._allocated.add(p)
            self._refs[p] = 1
            self.high_water = max(self.high_water, len(self._allocated))
            return p

    def ref(self, page: int) -> None:
        """Take one more reference on an allocated page (a slot
        attaching a shared prefix page, the trie indexing a slot's
        page, a CoW-source pin)."""
        with self._lock:
            if page not in self._allocated:
                raise ValueError(
                    f"page {page} ref'd but not allocated — the "
                    "refcount plumbing lost track of it")
            self._refs[page] += 1
            if self._refs[page] == 2 and page in self._indexed:
                self._reclaimable -= 1

    def index(self, page: int) -> None:
        """The prefix index holds one of ``page``'s refs from now on
        (called after its :meth:`ref`)."""
        with self._lock:
            self._indexed.add(page)
            self._reclaimable += self._refs[page] == 1

    def unindex(self, page: int) -> None:
        """The prefix index is about to :meth:`free` its ref."""
        with self._lock:
            self._indexed.discard(page)
            self._reclaimable -= self._refs[page] == 1

    @property
    def reclaimable(self) -> int:
        """Indexed pages whose only holder is the index (refcount 1):
        what an eviction loop could return to the free list."""
        with self._lock:
            return self._reclaimable

    def refcounts(self) -> Dict[int, int]:
        """{page: refcount}, one copy under one lock: a walk over many
        pages reads it where a :meth:`refcount` a page took the lock
        each time."""
        with self._lock:
            return dict(self._refs)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refs.get(page, 0)

    def refcount_histogram(self) -> Dict[int, int]:
        """{refcount: page count} over allocated pages — the flight
        bundle's sharing picture."""
        with self._lock:
            hist: Dict[int, int] = {}
            for c in self._refs.values():
                hist[c] = hist.get(c, 0) + 1
            return hist

    def free(self, pages) -> None:
        with self._lock:
            for p in pages:
                if p not in self._allocated:
                    raise ValueError(
                        f"page {p} returned to the pool but not "
                        "allocated — double free, refcount underflow "
                        "or foreign page id")
                self._refs[p] -= 1
                if self._refs[p] == 1 and p in self._indexed:
                    self._reclaimable += 1
                if self._refs[p] == 0:
                    del self._refs[p]
                    self._allocated.discard(p)
                    self._free_list.append(p)

    def accounting(self) -> dict:
        with self._lock:
            return {"total_usable": self.usable,
                    "free": len(self._free_list),
                    "allocated": len(self._allocated),
                    "leaked": self.usable - len(self._free_list)
                    - len(self._allocated),
                    "refs_total": sum(self._refs.values()),
                    "shared": sum(1 for c in self._refs.values()
                                  if c > 1),
                    "high_water": self.high_water}


class GenRequest:
    """Future-like handle for one generation request.

    ``get()`` blocks for completion and returns the generated token ids
    (including the eos token when one stopped it). A CANCELLED request
    (client disconnect) settles with the tokens generated so far — the
    stream semantics. Deadline expiry / server shutdown settle with the
    typed serving errors. ``cancel()`` is safe from any thread at any
    time; the engine observes it at the next iteration and returns the
    request's pages to the pool. ``prefix_hit_pages`` /
    ``accepted_tokens`` carry the round-9 per-request accounting into
    the /generate response (serving/http.py)."""

    def __init__(self, prompt, max_new_tokens: int,
                 eos_id: Optional[int], deadline: Optional[float],
                 now: float, trace_id: Optional[str] = None):
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new_tokens)
        self.eos_id = eos_id
        self.deadline = deadline          # absolute time.monotonic()
        # the request's end-to-end correlation id: every flight/journal
        # record this request touches carries it
        self.trace_id = trace_id or obs_context.new_trace_id()
        self.tokens: List[int] = []
        self.state = "waiting"  # waiting|running|done|cancelled|failed
        self.error: Optional[ServingError] = None
        self.done = threading.Event()
        self.submitted_at = now
        # engine clock at the FIRST admission to a slot (a preempted
        # request keeps it): admitted_at - submitted_at is queue wait
        self.admitted_at: Optional[float] = None
        # engine clock of each committed token, parallel to ``tokens``
        self.token_times: List[float] = []
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.evictions = 0
        self.prefix_hit_pages = 0
        self.accepted_tokens = 0
        self._cancelled = False

    @property
    def num_generated(self) -> int:
        return len(self.tokens)

    def cancel(self) -> None:
        self._cancelled = True

    def get(self, timeout: Optional[float] = None) -> List[int]:
        if timeout is None and self.deadline is not None:
            timeout = max(self.deadline - time.monotonic(), 0.0) + 0.25
        if not self.done.wait(timeout):
            raise Expired("generation still in flight past its "
                          "deadline/timeout")
        if self.error is not None:
            raise self.error
        return list(self.tokens)


class _Slot:
    """Host bookkeeping for one occupied decode slot."""

    __slots__ = ("req", "replay", "pos", "pages", "arrival",
                 "last_tok", "last_token_t", "draft_pos")

    def __init__(self, req: GenRequest, arrival: int):
        self.req = req
        # prompt + already-generated tokens: teacher-forced back through
        # the step on (re-)admission, so an evicted request's greedy
        # continuation is exactly what it would have produced unevicted
        self.replay = req.prompt + req.tokens
        self.pos = 0                     # next position to feed
        self.pages: List[int] = []
        self.arrival = arrival
        self.last_tok = 0
        self.last_token_t: Optional[float] = None
        # committed tokens already teacher-forced through the DRAFT
        # cache lane (speculative decoding); rolled back past rejected
        # proposals every verify
        self.draft_pos = 0

    def next_input(self) -> int:
        if self.pos < len(self.replay):
            return self.replay[self.pos]
        return self.last_tok


class _Fed:
    """One live slot of a dispatched step, as its landing needs it: the
    slot with its request (never looked up in ``DecodeEngine.slots``
    again: the slot index may have a new tenant by then), the position
    its first token was fed at, the tokens, whether they were a replay
    chunk, and the flat row of the step's choices that holds its (first)
    choice, or None where the chunk ended before the replay's tail."""

    __slots__ = ("s", "slot", "pos", "toks", "chunk", "row")

    def __init__(self, s, slot, pos, toks, chunk, row):
        self.s, self.slot, self.pos = s, slot, pos
        self.toks, self.chunk, self.row = toks, chunk, row


class _Step:
    """What :meth:`DecodeEngine._launch` sent: the device's futures (the
    choices, an expert model's load sums), the step's number, and a
    :class:`_Fed` a live slot. ``landed`` once its values were committed,
    or given up with a failed step. What the dry checks and the step's
    timing need (:meth:`DecodeEngine._look`): whether it runs the lane
    program, was launched with a step in flight and found that one
    finished (``dry``); the host clock (ns) of the last look that saw it
    running (``busy_at``; at first its enqueue) and the first that saw it
    finished (``done_at``, in phase ``done_in``), the collector's total
    pause at ``busy_at``, and where the device's queue puts its end
    (``model_end``; None where no step of its program was timed)."""

    __slots__ = ("step", "nxt", "load", "fed", "landed", "lanes", "ahead",
                 "dry", "busy_at", "done_at", "done_in", "gc_ns", "model_end")

    def __init__(self, step, nxt, load, fed, lanes=False):
        self.step, self.nxt, self.load, self.fed = step, nxt, load, fed
        self.landed = False
        self.lanes, self.ahead, self.dry = lanes, False, False
        self.busy_at = self.gc_ns = 0
        self.done_at = self.done_in = self.model_end = None


class DecodeEngine:
    """Persistent continuous-batching decode loop (see module doc).

    ``decoder`` is a models.TransformerDecoder (the dense reference
    path); the engine builds its PagedDecoder twin over the same
    parameter table. ``num_pages`` defaults to full capacity (every
    slot can reach ``max_seq_len``) — size it SMALLER to serve more
    slots than worst-case memory would allow and let preemption absorb
    the tail. ``draft``/``spec_k`` turn on speculative decoding (a
    second, smaller TransformerDecoder proposing ``spec_k`` tokens per
    step — greedy only); ``prefix_cache`` toggles shared-prefix KV
    reuse. Construction is cheap; the single XLA compile per jitted
    function happens on first use.

    How a prompt is fed: a slot with more than ``window`` replay tokens
    left (its prompt past the prefix match, or a preempted request's
    prompt + generated tokens) takes free PREFILL LANES of the step,
    oldest arrival first, as many as its remainder fills; the lanes'
    shape is the cache kind's (``paged.lanes``). A slot no lane is left
    for feeds its ``window`` tokens in the slot group as it always did,
    so no request waits for a lane. A step that feeds a lane dispatches
    the lane program, every other step the plain one.

    Drive it synchronously (``step()`` / ``run()`` — deterministic, the
    test/bench mode) or as a background thread (``start()`` /
    ``shutdown()`` — the serving mode; InferenceServer wires this).

    A step has two halves. :meth:`_launch` reaps, admits, plans,
    dispatches and writes down everything that is arithmetic on what the
    host holds (positions, pages, counters, the state snapshots);
    :meth:`_land` waits for the step's choices and commits the VALUES
    (tokens, stamps, EOS and ``max_new``, ``_finish``). ``step()`` is
    ``_land(_launch())``: one call, one step, committed at return. The
    thread keeps ONE step in flight: it launches step N+1 while step N
    runs, then lands N, so the device finds its next step queued and the
    host's work hides behind the device's. A decoding row of N+1 takes
    its input, N's choice, on the device (``PagedDecoder.feed``). Who
    drives decides the order of the halves; no argument does. What the
    host cannot know before N lands makes ``_launch`` land N first (a
    drain, counted in ``ahead_drains``): a draft (acceptance decides
    positions), a spill store (its pages go through host memory), a
    preemption (the victim's replay is ``prompt + tokens``). EOS is the
    one stop the host learns a step late: the row N+1 ran for that slot
    is computed and DROPPED, never appended, stamped or delivered, and
    the slot is free from step N+2."""

    def __init__(self, decoder, *, num_slots: int = 4,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 max_waiting: int = 64,
                 temperature: Optional[float] = None,
                 latency_window: int = 2048,
                 clock: Callable[[], float] = time.monotonic,
                 draft=None, spec_k: int = 0,
                 prefix_cache: bool = True,
                 attention: str = "auto",
                 warm_start: bool = True,
                 kv_quant: Optional[str] = None,
                 kv_spill_pages: int = 0,
                 state_snapshots: Optional[int] = None):
        pos_rows = decoder.max_positions
        if draft is not None and spec_k:
            decoder.require("speculation")
        if kv_spill_pages:
            decoder.require("spill")
        if max_seq_len is None:
            max_seq_len = pos_rows
        self.max_seq_len = min(int(max_seq_len), pos_rows)
        self.page_size = int(page_size)
        self.num_slots = int(num_slots)
        self.spec_k = max(int(spec_k), 0) if draft is not None else 0
        if self.spec_k and temperature is not None:
            raise ValueError(
                "speculative decoding is greedy-only: the acceptance "
                "rule is token identity, which sampling breaks")
        # W = spec_k + 1: one pending token + k proposals per dispatch.
        # Fixed at construction so churn never changes the jitted shape.
        self.window = 1 + self.spec_k
        pages_per_slot = -(-self.max_seq_len // self.page_size)
        if num_pages is None:
            num_pages = self.num_slots * pages_per_slot + 1
        self.warm_start = bool(warm_start)
        self.kv_quant = kv_quant
        self.paged = decoder.paged(
            num_slots=self.num_slots, page_size=self.page_size,
            num_pages=int(num_pages),
            max_pages_per_slot=pages_per_slot, temperature=temperature,
            window=self.window, attention=attention,
            warm_start=self.warm_start, kv_quant=kv_quant,
            state_snapshots=state_snapshots)
        if kv_quant is not None and not self.paged.use_kernel:
            # int8 pages without the dequant-fused kernel: attention
            # reads through the dequantizing gather (exact einsum) —
            # correct, just full-table-width traffic. Journaled once at
            # construction so a fleet-wide scrape can spot replicas
            # paying the fallback.
            journal_emit("engine", "dequant_fallback",
                         reason="kernel_unsupported", kv_quant=kv_quant)
        self.pool = PagePool(int(num_pages))
        # the pools are the decoder's business: whatever TWO pytrees
        # init_pools() gives (per-head K and V, the int8 pytrees, a latent
        # block's one pool and an empty pytree, page pools and a state's
        # rows) are only ever handed back, whole, to its step and its
        # page and row programs
        self.k_pool, self.v_pool = self.paged.init_pools()
        # a cache kind that keeps a recurrent state a slot beside its
        # pages (``state_rows``): slot s's state is row s of its state
        # pool, the kind's snapshot rows are the prefix index's to give
        # out, and every state_* step below is skipped for a kind without
        self._state = self.paged.cache if self.paged.cache.state_rows \
            else None
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(self.pool, self.page_size,
                        self._state.snapshot_rows if self._state else ())
            if prefix_cache else None)
        if kv_spill_pages and not prefix_cache:
            raise ValueError(
                "kv_spill_pages needs the prefix cache: spilled pages "
                "are keyed and restored by their trie token path")
        self.spill: Optional[SpillStore] = (
            SpillStore(int(kv_spill_pages)) if kv_spill_pages else None)
        # chaos seam (testing/faults.py family (s)): called at the
        # "read" and "commit" stages of every spill — kill_during_spill
        # raises WorkerCrash here to prove the ordering contract
        self._spill_interceptor: Optional[
            Callable[[str, tuple, int], None]] = None
        self.draft = None
        if draft is not None and self.spec_k > 0:
            from paddle_tpu.models.decode import DraftDecoder
            self.draft = DraftDecoder(
                draft, num_slots=self.num_slots,
                max_seq_len=self.max_seq_len, window=self.window,
                warm_start=self.warm_start)
            self._draft_kc, self._draft_vc = self.draft.init_caches()
        self.max_waiting = int(max_waiting)
        self.temperature = temperature
        self._clock = clock
        S, P, W = self.num_slots, pages_per_slot, self.window
        self.slots: List[Optional[_Slot]] = [None] * S
        self._tokens = np.zeros((S, W), np.int32)
        self._positions = np.zeros((S, W), np.int32)
        self._tables = np.zeros((S, P), np.int32)
        self._active = np.zeros((S, W), np.bool_)
        # the lane program's own input, a row a lane: (slot,
        # first position, tokens fed, the tokens) — PagedDecoder._step_impl
        self._lane_shape = lanes, width = self.paged.lanes
        self._lanes = np.zeros((lanes, 3 + width), np.int32)
        self._waiting: deque = deque()  # ptlint: guarded-by(serving.engine)
        self._cv = named_condition("serving.engine")
        self._accepting = True
        self._stopping = False
        self._close_now = False
        self._thread: Optional[threading.Thread] = None
        self._step_interceptor: Optional[Callable[[int], None]] = None
        self._steps = 0
        self._arrival_seq = 0
        self._active_steps_sum = 0
        self._cache_tokens_read = 0
        self._lat: deque = deque(maxlen=int(latency_window))
        self._ttft: deque = deque(maxlen=256)
        self._counters = {"submitted": 0, "finished": 0, "cancelled": 0,
                          "expired": 0, "preemptions": 0,
                          "rejected_queue": 0, "rejected_capacity": 0,
                          "closed": 0, "step_failures": 0,
                          "tokens_out": 0, "prefill_tokens": 0,
                          # every row fed, slot group and lanes; steps
                          # dispatched with the lane program, the prompt
                          # tokens they fed through lanes (of
                          # prefill_tokens) and those rows' cache reads
                          # (of cache_tokens_read)
                          "tokens_fed": 0, "prefill_lane_steps": 0,
                          "prefill_lane_tokens": 0,
                          "prefill_lane_cache_tokens_read": 0,
                          "prefix_hit_pages": 0, "prefix_miss_pages": 0,
                          "prefix_cow_copies": 0,
                          "prefix_evicted_pages": 0,
                          "spec_proposed_tokens": 0,
                          "spec_accepted_tokens": 0,
                          "kv_pages_spilled": 0,
                          "kv_pages_restored": 0,
                          "kv_spill_integrity_drops": 0,
                          "kv_spill_cleared": 0,
                          # first admissions and their summed wait
                          # (submit -> slot), on the engine's clock
                          "admitted": 0, "queue_wait_ns": 0,
                          # a share-aware expert layer's load, summed
                          # over the step's expert layers by the step
                          # itself: token-expert assignments of active
                          # tokens that fell on HELD experts, held
                          # experts that got at least one, and expert
                          # layers x steps to divide by (all 0 for a
                          # model without such layers)
                          "expert_assignments_held": 0,
                          "expert_hits_held": 0,
                          "expert_layer_steps": 0,
                          # a cache kind with a recurrent state a slot:
                          # slot-steps that read and wrote a state (a
                          # slot fed by several lanes counted once a
                          # step), snapshots copied out of a slot's row
                          # (those a node gave up again are the prefix
                          # index's count, ``state_snapshots_evicted`` in
                          # stats()), tokens a
                          # snapshot let an admission skip, and tokens
                          # the page trie matched that were fed again
                          # for want of a snapshot (all 0 without)
                          "state_rows_stepped": 0,
                          "state_snapshots_taken": 0,
                          "snapshot_attach_tokens": 0,
                          "snapshot_miss_tokens": 0,
                          # host nanoseconds by phase of step()/_loop:
                          # each is written by _phase() with the span of
                          # the same boundary (PERF.md section 3)
                          "host_admit_ns": 0, "host_plan_ns": 0,
                          "host_dispatch_ns": 0, "host_sync_ns": 0,
                          "host_commit_ns": 0, "host_idle_ns": 0,
                          # steps dispatched while the step before was
                          # still in flight, and steps that landed it
                          # first (a draft, a spill store, a preemption:
                          # the reason is in the flight record); the
                          # rest of ``steps`` found nothing in flight
                          "steps_launched_ahead": 0, "ahead_drains": 0,
                          # launches ahead that found the step in flight
                          # finished (the device ran dry before them), by
                          # the phase whose end first saw it so, and the
                          # device's idle time before them, estimated
                          # (_look, _dry)
                          "steps_launched_dry": 0, "dry_in_sync": 0,
                          "dry_in_commit": 0, "dry_in_admit": 0,
                          "dry_in_plan": 0, "dry_in_dispatch": 0,
                          "device_dry_ns": 0,
                          # steps that ran back to back on the device,
                          # timed between two syncs that waited, by
                          # program (_timed)
                          "steps_timed_plain": 0, "step_ns_plain": 0,
                          "steps_timed_lanes": 0, "step_ns_lanes": 0}
        # the dispatched step whose values have not landed (_launch sets
        # it, _land clears it), and the rows of the next step's tokens
        # that are fed from its choices on the device (-1: the host's)
        self._in_flight: Optional[_Step] = None
        self._src = np.full((S,), -1, np.int32)
        # the last landed step's (number, whether its sync's end is a
        # reading of the device's clock, that end: _timed), and the last
        # timed step of each program
        # (ns; 0: none yet), indexed by whether it ran the lane program
        self._last_sync: Optional[tuple] = None
        self._step_ns = [0, 0]
        import jax
        self._key0 = jax.random.PRNGKey(0)
        # live-state provider for postmortem bundles: the slot table
        # and wait queue by trace_id at dump time. Weakref'd so dead
        # engines never pin themselves in the recorder.
        import weakref
        ref = weakref.ref(self)

        def _flight_state():
            eng = ref()
            if eng is None:
                return None
            slots = [
                None if sl is None else
                {"trace_id": sl.req.trace_id, "pos": sl.pos,
                 "generated": sl.req.num_generated,
                 "pages": len(sl.pages)}
                for sl in list(eng.slots)]
            with eng._cv:
                waiting = [r.trace_id for r in eng._waiting]
                steps = eng._steps
            # prefix summary AFTER _cv release: lock order is
            # engine -> prefix -> pagepool, never held together here
            prefix = eng.prefix.summary() \
                if eng.prefix is not None else None
            return {"slots": slots, "waiting_trace_ids": waiting,
                    "steps": steps,
                    "pages": eng.pool.accounting(),
                    "prefix": prefix}

        FLIGHT.register_state_provider(f"engine-{id(self):x}",
                                       _flight_state)

        # performance plane (obs/profile.py + obs/slo.py): page-pool
        # occupancy rides the off-thread memory sampler, and stats()
        # (with a derived tokens_per_s) feeds the watchdog's
        # declarative objectives. Same weakref discipline as above.
        def _pool_accounting():
            eng = ref()
            return None if eng is None else eng.pool.accounting()

        PROFILER.register_pool(f"engine-{id(self):x}", _pool_accounting)

        rate_state = {"t": None, "tokens": 0}

        def _slo_stats():
            eng = ref()
            if eng is None:
                return None
            s = eng.stats()
            now = eng._clock()
            t0, tok0 = rate_state["t"], rate_state["tokens"]
            tokens = s.get("tokens_out", 0)
            rate_state["t"], rate_state["tokens"] = now, tokens
            if t0 is not None and now > t0:
                s["tokens_per_s"] = (tokens - tok0) / (now - t0)
            return s

        from paddle_tpu.obs.slo import WATCHDOG
        WATCHDOG.add_source(f"engine-{id(self):x}", _slo_stats)

    @contextlib.contextmanager
    def _phase(self, span: str, counter: Optional[str] = None):
        """One phase boundary of the loop, drawn once: the
        ``stat_timer`` span (TraceAnnotation + TRACER + StatItem) and
        the ``host_*_ns`` counter of ``stats()`` take the same start
        and end, so a trace and the counters cannot disagree about
        where a phase lies. Counters are always on; the span costs
        nothing more than ``stat_timer`` does when no trace is."""
        t0 = time.perf_counter_ns()
        try:
            with stat_timer(span):
                yield
        finally:
            if counter is not None:
                self._counters[counter] += time.perf_counter_ns() - t0

    # ------------------------------------------------------------ admission
    def _pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    def _retry_hint(self) -> float:
        lats = list(self._lat)
        per_tok = (sum(lats) / len(lats)) if lats else 0.005
        return max(per_tok * self.page_size, 0.01)

    def submit(self, prompt, max_new_tokens: int, *,
               eos_id: Optional[int] = None,
               deadline: Optional[float] = None,
               trace_id: Optional[str] = None) -> GenRequest:
        """Admit one generation request. Raises the serving-typed
        errors at admission (``Rejected`` reasons: ``kv_capacity`` for
        a request the pool could NEVER hold, ``queue_full`` for a
        saturated wait queue); the request itself settles with tokens
        or a typed error. ``trace_id`` correlates the request through
        admission → slot → every decode step → settle (minted here
        when the front passed none)."""
        now = self._clock()
        trace_id = trace_id or obs_context.current().trace_id \
            or obs_context.new_trace_id()
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt or int(max_new_tokens) < 1:
            raise ValueError("need a non-empty prompt and "
                             "max_new_tokens >= 1")
        total = len(prompt) + int(max_new_tokens)
        abs_deadline = (time.monotonic() + deadline) \
            if deadline is not None else None
        with self._cv:
            if not self._accepting:
                raise ServerClosed("decode engine is draining or "
                                   "stopped")
            if total > self.max_seq_len or \
                    self._pages_for(total) > self.pool.usable:
                self._counters["rejected_capacity"] += 1
                FLIGHT.record("mark", "engine/reject",
                              trace_id=trace_id, reason="kv_capacity")
                raise Rejected(
                    f"request needs {total} positions "
                    f"({self._pages_for(total)} KV pages) but the "
                    f"engine serves at most {self.max_seq_len} "
                    f"positions / {self.pool.usable} pages — it can "
                    "never be scheduled; shorten it",
                    retry_after=0.0, reason="kv_capacity")
            if len(self._waiting) >= self.max_waiting:
                self._counters["rejected_queue"] += 1
                retry = self._retry_hint()
                FLIGHT.record("mark", "engine/reject",
                              trace_id=trace_id, reason="queue_full")
                raise Rejected(
                    f"generation queue full ({self.max_waiting}); "
                    f"retry in {retry:.2f}s", retry_after=retry,
                    reason="queue_full")
            req = GenRequest(prompt, max_new_tokens, eos_id,
                             abs_deadline, now, trace_id=trace_id)
            self._counters["submitted"] += 1
            self._waiting.append(req)
            self._cv.notify_all()
        FLIGHT.record("mark", "engine/submit", trace_id=trace_id,
                      prompt_len=len(prompt),
                      max_new=int(max_new_tokens))
        return req

    # ------------------------------------------------------------ scheduling
    def _settle(self, req: GenRequest, state: str,
                error: Optional[ServingError] = None) -> None:
        req.state = state
        req.error = error
        req.finished_at = self._clock()
        req.done.set()

    def _index_slot_pages(self, slot: _Slot) -> None:
        """Leave the slot's COMPLETE teacher-forced pages behind in the
        prefix index (finish AND evict paths). Only rows the slot has
        actually FED are covered — ``seq[:pos]`` excludes rejected
        speculation rows and the not-yet-fed pending token."""
        if self.prefix is None or not slot.pages:
            return
        seq = slot.req.prompt + slot.req.tokens
        self.prefix.insert(seq[:slot.pos], slot.pages)

    def _copy_state(self, src: int, dst: int) -> None:
        """State row ``src`` -> ``dst`` on the device (a snapshot taken
        or given to a slot, a slot's row zeroed)."""
        with self._phase("serving/state_copy"):
            self.k_pool, self.v_pool = self.paged.copy_state(
                self.k_pool, self.v_pool, src, dst)

    def _snapshot(self, s: int, slot: _Slot) -> None:
        """Slot ``s`` stands on a page boundary: leave its full pages in
        the prefix index now and, where the node of the last has no
        snapshot yet, a copy of its state row as that node's. A boundary
        is snapshotted where a replay's last one is reached (the planner
        cuts the chunk there) and where a request ends or is preempted on
        one: a slot that decodes past boundaries leaves none behind
        (a copy every page_size tokens a slot)."""
        if self._state is None or self.prefix is None or not slot.pos \
                or slot.pos % self.page_size:
            return
        seq = (slot.req.prompt + slot.req.tokens)[:slot.pos]
        self.prefix.insert(seq, slot.pages)
        if self.prefix.has_snapshot(seq) is not False:
            return                      # one is there, or no such node
        row = self.prefix.take_snapshot_row()
        if row is None:
            return
        try:
            self._copy_state(s, row)
        except Exception as e:          # pools rebuilt on next dispatch
            self.prefix.free_snapshot_row(row)
            journal_emit("engine", "state_copy_failure",
                         error=repr(e)[:200], trace_id=slot.req.trace_id)
            return
        if self.prefix.set_snapshot(seq, row):
            self._counters["state_snapshots_taken"] += 1

    def _finish(self, s: int, state: str,
                error: Optional[ServingError] = None,
                snapshot: bool = True) -> None:
        """Release slot ``s``: pages to the prefix index then back to
        the pool FIRST (the no-leak invariant), then settle the
        request. ``snapshot`` False where the slot's state row has moved
        past ``slot.pos`` (a dead row after EOS, :meth:`_land`)."""
        slot = self.slots[s]
        if state in ("done", "cancelled"):
            # failed/closed slots may hold garbage KV (step failure) —
            # never index those pages
            self._index_slot_pages(slot)
            if snapshot:
                self._snapshot(s, slot)
        self.pool.free(slot.pages)
        slot.pages = []
        self._tables[s, :] = 0
        self._active[s, :] = False
        self._tokens[s, :] = 0
        self._positions[s, :] = 0
        self.slots[s] = None
        counter = {"done": "finished", "cancelled": "cancelled",
                   "failed": "failed", "closed": "closed"}.get(state)
        if state == "done":
            self._counters["finished"] += 1
        elif state == "cancelled":
            self._counters["cancelled"] += 1
        elif isinstance(error, Expired):
            self._counters["expired"] += 1
        elif isinstance(error, ServerClosed):
            self._counters["closed"] += 1
        if counter:
            global_counters.bump(f"serving/decode_{counter}")
        FLIGHT.record("mark", "engine/settle",
                      trace_id=slot.req.trace_id, state=state,
                      slot=s, generated=slot.req.num_generated,
                      error=repr(error)[:200] if error else None)
        self._settle(slot.req, state, error)
        with self._cv:
            self._cv.notify_all()

    def _evict(self, s: int) -> None:
        """Preempt slot ``s``: complete pages into the prefix index
        (re-admission walks them right back — preemption cost shrinks
        to the incomplete tail), refs to the pool, request back to the
        FRONT of the wait queue (it keeps its generated tokens and
        replays them on re-admission — greedy output is unchanged)."""
        slot = self.slots[s]
        self._index_slot_pages(slot)
        self._snapshot(s, slot)
        self.pool.free(slot.pages)
        slot.pages = []
        self._tables[s, :] = 0
        self._active[s, :] = False
        self.slots[s] = None
        req = slot.req
        req.state = "waiting"
        req.evictions += 1
        self._counters["preemptions"] += 1
        global_counters.bump("serving/decode_preemptions")
        journal_emit("engine", "preemption",
                     generated=req.num_generated,
                     evictions=req.evictions,
                     free_pages=self.pool.free_pages,
                     trace_id=req.trace_id)
        with self._cv:
            self._waiting.appendleft(req)

    def _reap(self, now: float) -> None:
        """Settle cancellations and deadline expiries — running slots
        and waiting requests both."""
        for s in range(self.num_slots):
            slot = self.slots[s]
            if slot is None:
                continue
            if slot.req._cancelled:
                self._finish(s, "cancelled")
            elif slot.req.deadline is not None and \
                    now > slot.req.deadline:
                self._finish(s, "failed", Expired(
                    f"deadline passed after {slot.req.num_generated} "
                    "generated tokens"))
        with self._cv:
            keep = deque()
            for req in self._waiting:
                if req._cancelled:
                    self._counters["cancelled"] += 1
                    FLIGHT.record("mark", "engine/settle",
                                  trace_id=req.trace_id,
                                  state="cancelled", where="waiting")
                    self._settle(req, "cancelled")
                elif req.deadline is not None and now > req.deadline:
                    self._counters["expired"] += 1
                    FLIGHT.record("mark", "engine/settle",
                                  trace_id=req.trace_id,
                                  state="expired", where="waiting")
                    self._settle(req, "failed", Expired(
                        "deadline passed while queued for a slot"))
                else:
                    keep.append(req)
            self._waiting = keep

    def _alloc_page(self) -> Optional[int]:
        """One page from the pool, reclaiming cold prefix-cache leaves
        (LRU, trie-only refcount) when the free list is dry — the trie
        gives pages back BEFORE any running request is preempted. With
        a spill store attached, cold pages route device->host
        (:meth:`_spill_cold_pages`) instead of being destroyed; the
        lossy ``evict_lru`` path remains the fallback when spilling
        can't free anything (no candidates, or a failed device read)."""
        page = self.pool.alloc()
        while page is None and self.prefix is not None:
            if self.spill is not None and self._spill_cold_pages(1):
                page = self.pool.alloc()
                continue
            freed = self.prefix.evict_lru(1)
            if not freed:
                return None
            self._counters["prefix_evicted_pages"] += len(freed)
            journal_emit("engine", "prefix_evict", pages=freed,
                         free_pages=self.pool.free_pages,
                         engine_step=self._steps)
            page = self.pool.alloc()
        return page

    # ------------------------------------------------------------- spill
    @staticmethod
    def _flatten_page(tag: str, tree, out: dict) -> None:
        """Pool-page pytree -> named host arrays (fp pools are bare
        arrays; int8 pools are {"q", "s"} dicts)."""
        if isinstance(tree, dict):
            for kk in sorted(tree):
                out[f"{tag}.{kk}"] = np.asarray(tree[kk])
        else:
            out[tag] = np.asarray(tree)

    @staticmethod
    def _unflatten_page(tag: str, like, payload: dict):
        import jax.numpy as jnp
        if isinstance(like, dict):
            return {kk: jnp.asarray(payload[f"{tag}.{kk}"])
                    for kk in like}
        return jnp.asarray(payload[tag])

    def _spill_cold_pages(self, n: int, avoid=None) -> int:
        """Spill up to ``n`` cold trie-only pages to the host store.
        The crash-safety ordering (serving/spill.py module doc): read
        + checksum first (no state changed), THEN evict the node and
        free the device page, THEN commit the entry — a crash at any
        point leaves the accounting balanced and can never leave a
        page both device-owned and host-stored.

        ``avoid`` (a token tuple) skips candidates on that path — the
        restore path passes the replay it is extending so making room
        can never spill the very match it is restoring into."""
        freed = 0
        cands = self.prefix.spill_candidates(
            n if avoid is None else n + 8)
        for path, page in cands:
            if freed >= n:
                break
            if avoid is not None and avoid[:len(path)] == path:
                continue
            hook = self._spill_interceptor
            if hook is not None:
                hook("read", path, page)
            try:
                payload: dict = {}
                k_page, v_page = self.paged.read_page(
                    self.k_pool, self.v_pool, page)
                self._flatten_page("k", k_page, payload)
                self._flatten_page("v", v_page, payload)
                entry = SpillEntry(payload)
            # ptlint: disable=R7(a failed device read falls back to the lossy evict path — the serving loop must not die for a cache optimization)
            except Exception as e:
                self._counters["kv_spill_integrity_drops"] += 1
                _SPILL_INTEGRITY.inc()
                journal_emit("engine", "spill_integrity",
                             reason="read_failed",
                             error=repr(e)[:200], page=page,
                             engine_step=self._steps)
                return freed
            if self.prefix.evict_exact(path) is None:
                continue               # node changed under us — skip
            if hook is not None:
                hook("commit", path, page)
            self.spill.put(path, entry)
            freed += 1
            self._counters["kv_pages_spilled"] += 1
            _SPILL_PAGES.inc()
            journal_emit("engine", "page_spill", page=page,
                         key_pages=len(path) // self.page_size,
                         spilled_now=len(self.spill),
                         free_pages=self.pool.free_pages,
                         engine_step=self._steps)
        return freed

    def _restore_spilled(self, replay) -> int:
        """Walk ``replay``'s token path past the trie match and restore
        consecutive spilled pages host->device BEFORE admission charges
        prefill — the spill-hit path of the two-tier cache. Each
        restore allocates a device page (which may cascade-spill colder
        pages), verifies the entry's checksum, uploads, and re-inserts
        the trie node; a torn entry is dropped and journaled
        (``engine/spill_integrity``) so the lookup degrades to a
        prefix miss."""
        ps = self.page_size
        limit = len(replay) - 1
        restored = 0
        avoid = tuple(int(t) for t in replay)
        while True:
            match = self.prefix.match(replay)
            nxt = match.matched + ps
            if nxt > limit:
                break
            key = avoid[:nxt]
            if not self.spill.has(key):
                break
            # make room by spilling colder OTHER branches only — never
            # the lossy evict path (destroying cache to restore cache)
            # and never this replay's own match (the ``avoid`` guard)
            page = self.pool.alloc()
            while page is None:
                if not self._spill_cold_pages(1, avoid=avoid):
                    break
                page = self.pool.alloc()
            if page is None:
                break                  # pool truly full — stay spilled
            entry = self.spill.pop(key)
            if entry is None:
                self.pool.free([page])
                break
            if not entry.verify():
                self.pool.free([page])
                self.spill.dropped_integrity += 1
                self._counters["kv_spill_integrity_drops"] += 1
                _SPILL_INTEGRITY.inc()
                journal_emit("engine", "spill_integrity",
                             reason="crc_mismatch",
                             key_pages=nxt // ps,
                             engine_step=self._steps)
                break
            try:
                k_page = self._unflatten_page("k", self.k_pool,
                                              entry.payload)
                v_page = self._unflatten_page("v", self.v_pool,
                                              entry.payload)
                self.k_pool, self.v_pool = self.paged.write_page(
                    self.k_pool, self.v_pool, k_page, v_page, page)
            # ptlint: disable=R7(a failed upload degrades to a prefix miss — never kills admission)
            except Exception as e:
                self.pool.free([page])
                self.spill.dropped_integrity += 1
                self._counters["kv_spill_integrity_drops"] += 1
                _SPILL_INTEGRITY.inc()
                journal_emit("engine", "spill_integrity",
                             reason="restore_write_failed",
                             error=repr(e)[:200], page=page,
                             engine_step=self._steps)
                break
            # trie takes the page over: insert refs it (2), dropping
            # our alloc ref leaves it trie-only (1) — exactly the
            # state it was spilled from
            self.prefix.insert(key, match.pages + [page])
            self.pool.free([page])
            self.spill.restored_count += 1
            restored += 1
            self._counters["kv_pages_restored"] += 1
            _SPILL_RESTORED.inc()
            journal_emit("engine", "page_restore", page=page,
                         key_pages=nxt // ps,
                         spilled_now=len(self.spill),
                         engine_step=self._steps)
        return restored

    def _attach_prefix(self, s: int, slot: _Slot, match) -> None:
        """Wire a PrefixMatch into slot ``s``: one slot ref per shared
        page, copy-on-write for an intra-page divergence, and the
        slot's feed position jumps past every matched token."""
        req = slot.req
        for p in match.pages:
            self.pool.ref(p)
            slot.pages.append(p)
        matched = match.matched
        if match.cow is not None:
            src, rows = match.cow
            # pin the source: the dst alloc below may reclaim trie
            # leaves, and the source IS a refcount-1 leaf right now
            self.pool.ref(src)
            dst = self._alloc_page()
            if dst is not None:
                try:
                    self.k_pool, self.v_pool = self.paged.copy_page(
                        self.k_pool, self.v_pool, src, dst)
                except Exception as e:  # pools rebuilt on next dispatch
                    self.pool.free([dst])
                    journal_emit("engine", "cow_copy_failure",
                                 error=repr(e)[:200],
                                 trace_id=req.trace_id)
                else:
                    slot.pages.append(dst)
                    matched += rows
                    self._counters["prefix_cow_copies"] += 1
                    _PREFIX_COW.inc()
                    if self.prefix is not None:
                        self.prefix.cow_hits += 1
            self.pool.free([src])       # unpin
        for j, p in enumerate(slot.pages):
            self._tables[s, j] = p
        slot.pos = matched
        hit = len(match.pages)
        miss = self._pages_for(len(slot.replay)) - hit
        self._counters["prefix_hit_pages"] += hit
        self._counters["prefix_miss_pages"] += max(miss, 0)
        if hit:
            _PREFIX_HIT.inc(hit)
        if miss > 0:
            _PREFIX_MISS.inc(miss)
        if self.prefix is not None:
            self.prefix.hit_pages += hit
            self.prefix.miss_pages += max(miss, 0)
        req.prefix_hit_pages = hit

    def _admit(self) -> None:
        """Waiting -> free slots, gated on FREE PAGES: the queue head
        takes a slot only when the pool can carry its NOVEL pages to
        its first new token — shared-prefix pages cost nothing, and
        reclaimable trie leaves count as free (minus the pages this
        very match would pin)."""
        with self._cv:
            for s in range(self.num_slots):
                if self.slots[s] is not None or not self._waiting:
                    continue
                req = self._waiting[0]
                replay = req.prompt + req.tokens
                if self.spill is not None and len(self.spill) and \
                        self.prefix is not None:
                    # spill-hit TTFT path: restored pages join the
                    # match below, so admission charges only what is
                    # NOVEL beyond both tiers
                    self._restore_spilled(replay)
                match = self.prefix.match(replay) \
                    if self.prefix is not None else None
                trie_matched = match.matched if match is not None else 0
                if match is not None and self._state is not None:
                    # worth only as deep as a snapshot of the state exists
                    match = match.cut_to_snapshot(self.page_size)
                shared = len(match.pages) if match is not None else 0
                need_now = self._pages_for(len(replay) + 1) - shared
                avail = self.pool.free_pages
                if self.prefix is not None:
                    avail += max(
                        0, self.prefix.reclaimable_pages() - shared)
                if need_now > avail:
                    break              # page-aware: head waits for pages
                self._waiting.popleft()
                req.state = "running"
                if req.admitted_at is None:     # not a re-admission
                    req.admitted_at = self._clock()
                    self._counters["admitted"] += 1
                    self._counters["queue_wait_ns"] += int(round(
                        (req.admitted_at - req.submitted_at) * 1e9))
                self._arrival_seq += 1
                slot = _Slot(req, self._arrival_seq)
                self.slots[s] = slot
                if match is not None and \
                        (match.pages or match.cow is not None):
                    self._attach_prefix(s, slot, match)
                if self._state is not None:
                    # the slot's row starts from the snapshot at its match
                    # or from zero, never from the row's last tenant
                    self._copy_state(
                        match.snapshots[-1] if match is not None
                        and match.pages else self._state.zero_row, s)
                    c = self._counters
                    c["snapshot_attach_tokens"] += slot.pos
                    c["snapshot_miss_tokens"] += trie_matched - slot.pos
                FLIGHT.record("mark", "engine/admit",
                              trace_id=req.trace_id, slot=s,
                              replay=len(replay),
                              prefix_tokens=slot.pos)

    def _ensure_pages(self, plan: Dict[int, List[int]]) -> bool:
        """Allocate each planned slot's pages through the LAST position
        its window will write; on pool exhaustion reclaim trie leaves
        first, then preempt the YOUNGEST slot (LIFO — oldest requests
        keep their progress) until the allocation succeeds. False, and
        nobody preempted, where a preemption is due while a step is in
        flight: a victim's replay is ``prompt + tokens``, and one token
        may still be on the device (:meth:`_launch` lands it and plans
        again; the pages allocated so far stay with their slots)."""
        for s in sorted(
                (i for i in range(self.num_slots)
                 if self.slots[i] is not None and i in plan),
                key=lambda i: self.slots[i].arrival):
            slot = self.slots[s]
            if slot is None:           # evicted by an earlier iteration
                continue
            last = slot.pos + len(plan[s]) - 1
            while len(slot.pages) * self.page_size <= last:
                page = self._alloc_page()
                if page is None:
                    if self._in_flight is not None:
                        return False
                    victims = sorted(
                        (i for i in range(self.num_slots)
                         if self.slots[i] is not None),
                        key=lambda i: -self.slots[i].arrival)
                    assert victims, "pool exhausted with no slot held"
                    self._evict(victims[0])
                    if self.slots[s] is None:
                        break          # evicted ourselves
                    continue
                slot.pages.append(page)
                self._tables[s, len(slot.pages) - 1] = page
        return True

    # ----------------------------------------------------------- speculation
    def _draft_propose(self, active_idx: List[int]) -> Dict[int, List[int]]:
        """Run the draft model for up to spec_k proposals per caught-up
        slot: bounded rounds of the draft's own [S, W] jitted step,
        each round teacher-forcing committed tokens the draft hasn't
        seen (up to W per round) or chaining one proposal. Slots still
        prefilling the TARGET are skipped — their draft lanes catch up
        across later steps at W tokens a round."""
        if self.draft is None:
            return {}
        S, W = self.num_slots, self.window
        props: Dict[int, List[int]] = {}
        want: Dict[int, int] = {}
        for s in active_idx:
            slot = self.slots[s]
            if slot.pos < len(slot.replay) - 1:
                continue               # target still prefilling
            req = slot.req
            seq_len = len(req.prompt) + len(req.tokens)
            k_eff = min(self.spec_k,
                        req.max_new - req.num_generated - 1,
                        self.max_seq_len - 1 - slot.pos,
                        self.max_seq_len + 1 - seq_len)
            if k_eff > 0:
                props[s] = []
                want[s] = k_eff
        if not props:
            return {}
        toks = np.zeros((S, W), np.int32)
        poss = np.zeros((S, W), np.int32)
        act = np.zeros((S, W), np.bool_)
        for _ in range(self.spec_k + 2):
            toks[:, :] = 0
            poss[:, :] = 0
            act[:, :] = False
            fed: Dict[int, int] = {}   # slot -> tokens fed this round
            for s, got in props.items():
                slot = self.slots[s]
                if len(got) >= want[s]:
                    continue
                seq = slot.req.prompt + slot.req.tokens
                dp = slot.draft_pos
                if dp < len(seq):      # catch-up: feed committed chunk
                    c = min(W, len(seq) - dp)
                    toks[s, :c] = seq[dp:dp + c]
                else:                  # chain: feed the last proposal
                    c = 1
                    toks[s, 0] = got[-1]
                poss[s, :c] = np.arange(dp, dp + c)
                act[s, :c] = True
                fed[s] = c
            if not fed:
                break
            try:
                out, self._draft_kc, self._draft_vc = self.draft.step(
                    self._draft_kc, self._draft_vc, toks, poss, act)
                out = np.asarray(out)
            # ptlint: disable=R7(draft failures must not kill the serving loop — the target path continues unassisted)
            except Exception as e:
                journal_emit("engine", "draft_failure",
                             error=repr(e)[:400],
                             engine_step=self._steps)
                self._draft_kc, self._draft_vc = \
                    self.draft.init_caches()
                for s in props:
                    if self.slots[s] is not None:
                        self.slots[s].draft_pos = 0
                return {}
            for s, c in fed.items():
                slot = self.slots[s]
                seq_len = len(slot.req.prompt) + len(slot.req.tokens)
                slot.draft_pos += c
                if slot.draft_pos >= seq_len:
                    # the last fed row predicts the next token: the
                    # first/next proposal in the chain
                    props[s].append(int(out[s, c - 1]))
        return {s: p for s, p in props.items() if p}

    # ------------------------------------------------------------- the loop
    def step(self) -> bool:
        """One engine iteration, whole: :meth:`_launch` (reap, admit,
        draft-propose, window-plan, page-ensure, ONE jitted target
        dispatch, the positions written down) then :meth:`_land` of that
        very step (the one sync, the tokens committed). Returns True iff
        a device step ran. Single-threaded by contract: the caller in
        sync mode (the engine's thread runs :meth:`_loop`, which lands
        the step BEFORE the one it launched). Each phase is one
        ``_phase``: a ``host_*_ns`` counter and a span under
        ``serving/step``, whose ``step`` is the ``engine_step`` of its
        slots' flight records."""
        with obs_context.bind(step=self._steps + 1), \
                self._phase("serving/step"), contextlib.ExitStack() as dev:
            rec = self._launch(dev)
            return rec is not None and self._land(rec, dev)

    def _pending(self, s: int) -> Optional[_Fed]:
        """Slot ``s``'s part of the step in flight if that step chooses a
        token for it (the one value the host lacks about the slot)."""
        rec = self._in_flight
        fed = rec.fed.get(s) if rec is not None else None
        if fed is None or fed.slot is not self.slots[s] or fed.row is None:
            return None
        return fed

    def _give_up_in_flight(self) -> None:
        """The step in flight will never land (its pools went with a
        failed step, or the engine closes now): its tokens are dropped
        with the requests it ran for, which settle where they stand."""
        if self._in_flight is not None:
            self._in_flight.landed = True
            self._in_flight = None

    def _drain(self, why: str) -> None:
        """Land the step in flight before this one is planned: its plan
        needs values the host does not have yet."""
        rec = self._in_flight
        FLIGHT.record("mark", "engine/ahead_drain", reason=why,
                      engine_step=rec.step)
        self._land(rec)

    def _launch(self, dev: contextlib.ExitStack) -> Optional[_Step]:
        """The first half of a step, everything that needs no value of
        the step in flight: reap, admit, plan (pages ensured), dispatch,
        and what the host can write down of the step it sent: the step
        count, positions, the counters of tokens fed and cache read, the
        flight records, a state snapshot where a chunk ends on its
        boundary (dispatched behind the step, so before the next step
        writes the row). -> the :class:`_Step` now in flight, or None
        (nothing live, or the dispatch failed and everything settled).
        ``dev`` takes the ``serving/decode_step`` span, which the
        caller's :meth:`_land` closes after its sync."""
        drained = self._in_flight is not None and (
            self.draft is not None or self.spill is not None)
        if drained:
            # acceptance decides the next positions; a spill store reads
            # and restores pages through host memory, keyed by tokens
            self._drain("draft" if self.draft is not None else "spill")
        with self._phase("serving/admit", "host_admit_ns"):
            interceptor = self._step_interceptor
            if interceptor is not None:
                interceptor(self._steps)
            self._reap(self._clock())
            self._admit()
        self._look("admit")
        with self._phase("serving/plan", "host_plan_ns"):
            planned = self._plan_windows()
        if planned is None:         # a preemption is due
            drained = True
            self._drain("preempt")
            with self._phase("serving/plan", "host_plan_ns"):
                planned = self._plan_windows()
        self._look("plan")
        plan, live, lane_of = planned
        older = self._in_flight
        if live or older is not None:
            # the turn's exchange with the device: this dispatch and
            # the caller's sync
            dev.enter_context(stat_timer("serving/decode_step"))
        if not live:
            return None
        try:
            # dispatch: the host-to-device transfers, the enqueue, and
            # what was sent written down
            with self._phase("serving/dispatch", "host_dispatch_ns"):
                key = self._key0
                if self.temperature is not None:
                    import jax
                    key = jax.random.fold_in(self._key0, self._steps)
                # copies: the next plan fills these arrays again while
                # this step may still be reading them (the CPU backend
                # takes an aligned numpy array without a copy)
                tokens = self._tokens.copy()
                self._look("dispatch")
                if (self._src >= 0).any():
                    tokens = self.paged.feed(older.nxt, self._src.copy(),
                                             tokens)
                nxt, self.k_pool, self.v_pool = self.paged.step(
                    self.k_pool, self.v_pool, tokens,
                    self._positions.copy(), self._tables.copy(),
                    self._active.copy(), key,
                    self._lanes.copy() if lane_of else None)
                # enqueued: the step in flight's last dry check (a pause
                # inside the enqueue itself, a collection or the
                # runtime's, leaves the device idle too)
                t_sent = time.perf_counter_ns()
                gc_sent = GC_WATCH.pause_ns
                dry = self._dry(older, t_sent) if older is not None \
                    else None
                rec = self._sent(plan, live, lane_of, nxt,
                                 self.paged.expert_counts)
        # ptlint: disable=R7(serving boundary — in-flight requests settle typed and the pools rebuild; the engine thread must never die)
        except Exception as e:
            self._recover_from_step_failure(e)
            return None
        self._counters["steps_launched_ahead"] += older is not None
        self._counters["ahead_drains"] += drained
        rec.ahead, rec.dry = older is not None, dry is not None
        rec.busy_at, rec.gc_ns = t_sent, gc_sent
        # the device's queue: the step starts at its enqueue or where the
        # step in flight ends, and takes the last timed step of its program
        start = t_sent if older is None or rec.dry else older.model_end
        step_ns = self._step_ns[rec.lanes]
        if start is not None and step_ns:
            rec.model_end = max(start, t_sent) + step_ns
        if rec.dry:
            self._count_dry(rec.step, *dry)
        self._in_flight = rec
        return rec

    def _ready(self, rec: _Step) -> bool:
        """Whether the device has finished step ``rec`` (does not block)."""
        return rec.nxt.is_ready()

    def _look(self, phase: str, t: Optional[int] = None) -> None:
        """A dry check at the end of ``phase`` (in ``dispatch``: before
        the enqueue and after it): has the step in flight finished? Until
        one look sees it finished, each look that sees it running moves
        its ``busy_at`` (and the collector's total) up; the first that
        sees it finished stamps ``done_at`` and the phase. Only under the
        thread is a step in flight while the host works; one
        ``is_ready()`` a look, which neither blocks nor polls."""
        rec = self._in_flight
        if rec is None or rec.done_at is not None:
            return
        t = time.perf_counter_ns() if t is None else t
        if self._ready(rec):
            rec.done_at, rec.done_in = t, phase
        else:
            rec.busy_at, rec.gc_ns = t, GC_WATCH.pause_ns

    def _dry(self, older: _Step, t: int) -> Optional[tuple]:
        """The last look at ``older``, once the step launched behind it is
        enqueued (``t``). -> None where it is still running, else the
        dry launch: (phase that first saw it finished, the device's idle
        estimate, the bracket's lower and upper bounds, the collector's
        pause inside the bracket), ns. The bracket runs from the first
        look that saw it finished, and from the last that saw it running,
        to the enqueue. The estimate is the enqueue less where the
        device's queue puts ``older``'s end (``model_end``: from the last
        sync that read the device's clock, a timed step of each program
        queued behind it), clamped into the bracket; without such an end,
        the lower bound."""
        self._look("dispatch", t)
        if older.done_at is None:
            return None
        lo, hi = t - older.done_at, t - older.busy_at
        est = lo if older.model_end is None \
            else min(max(t - older.model_end, lo), hi)
        return older.done_in, est, lo, hi, GC_WATCH.pause_ns - older.gc_ns

    def _count_dry(self, step: int, phase: str, est: int, lo: int, hi: int,
                   gc_ns: int) -> None:
        c = self._counters
        c["steps_launched_dry"] += 1
        c["dry_in_" + phase] += 1
        c["device_dry_ns"] += est
        FLIGHT.record("mark", "engine/dry", engine_step=step, phase=phase,
                      est_ms=est / 1e6, lo_ms=lo / 1e6, hi_ms=hi / 1e6,
                      gc_ms=gc_ns / 1e6)

    def _timed(self, rec: _Step, clock: bool, t: int) -> None:
        """``rec``'s sync ended at ``t``; ``clock``: ``t`` is where ``rec``
        ended on the device (the sync waited for it, and no pause held its
        return: :meth:`_land`). A step whose sync and its predecessor's
        are such readings, launched ahead and not dry, ran back to back
        with that predecessor: its device time is the distance between the
        two syncs' ends, counted by its program. Such a reading also puts
        the end of the step queued behind ``rec`` a timed step of its
        program later."""
        last, self._last_sync = self._last_sync, (rec.step, clock, t)
        if clock and rec.ahead and not rec.dry and last is not None \
                and last[0] == rec.step - 1 and last[1]:
            ns, key = t - last[2], ("plain", "lanes")[rec.lanes]
            self._counters["steps_timed_" + key] += 1
            self._counters["step_ns_" + key] += ns
            self._step_ns[rec.lanes] = ns
        nxt = self._in_flight
        if clock and nxt is not None and nxt.step == rec.step + 1 \
                and nxt.ahead and not nxt.dry and self._step_ns[nxt.lanes]:
            nxt.model_end = t + self._step_ns[nxt.lanes]

    def _plan_windows(self):
        """The step's host plan: each active slot's tokens (a replay
        chunk through prefill lanes or the slot's window, or the pending
        token + the draft's proposals), its pages ensured (which may
        preempt), and the step's small int32 inputs filled. -> (plan,
        live slot indices, {lane-fed slot: its lanes}); nothing live
        means no dispatch, no lane-fed slot the plain program. None where
        a preemption is due while a step is in flight
        (:meth:`_ensure_pages`). With a step in flight a slot whose last
        token (by ``max_new``) that step chooses is not planned, and a
        decoding slot's pending token is that step's choice: ``_src``
        names its row, and the device feeds it."""
        active_idx = [s for s in range(self.num_slots)
                      if self.slots[s] is not None]
        if not active_idx:
            return {}, [], {}
        props = self._draft_propose(active_idx)
        # window plan: a replay chunk (multi-token prefill) or the
        # pending token + the draft's proposals (speculative verify)
        W = self.window
        plan: Dict[int, List[int]] = {}
        src: Dict[int, int] = {}
        for s in active_idx:
            slot = self.slots[s]
            if slot.pos < len(slot.replay) - 1:
                wlen = min(W, len(slot.replay) - slot.pos)
                plan[s] = slot.replay[slot.pos:slot.pos + wlen]
                continue
            pending = self._pending(s)
            if pending is not None:
                if slot.req.num_generated + 1 >= slot.req.max_new:
                    continue            # its last token is in flight
                src[s] = pending.row
            p_s = props.get(s, [])[:W - 1]
            room = self.max_seq_len - 1 - slot.pos
            plan[s] = [0 if pending is not None else slot.next_input()] \
                + p_s[:max(room, 0)]
        # prefill lanes: a slot with more replay left than its window
        # holds takes free lanes, oldest first, as many as its remainder
        # fills (consecutive lanes of one slot are one longer chunk); a
        # slot no lane is left for keeps the window planned above
        n_lanes, width = self._lane_shape
        lane_of: Dict[int, range] = {}
        taken = 0
        for s in sorted((s for s in active_idx if n_lanes and
                         len(self.slots[s].replay) - self.slots[s].pos > W),
                        key=lambda s: self.slots[s].arrival):
            if taken == n_lanes:
                break
            slot = self.slots[s]
            left = len(slot.replay) - slot.pos
            if self._state is not None:
                # a chunk ends ON the replay's last page boundary, where
                # the state is snapshotted, before it goes past it
                last = len(slot.replay) // self.page_size * self.page_size
                if slot.pos < last:
                    left = last - slot.pos
            lane_of[s] = range(taken, min(n_lanes, taken + -(-left // width)))
            taken = lane_of[s].stop
            plan[s] = slot.replay[
                slot.pos:slot.pos + min(left, len(lane_of[s]) * width)]
        if not self._ensure_pages(plan):
            return None
        live = [s for s in active_idx
                if self.slots[s] is not None and s in plan]
        lane_of = {s: ln for s, ln in lane_of.items() if s in live}
        if not live:
            return plan, live, lane_of
        self._active[:, :] = False
        self._tokens[:, :] = 0
        self._positions[:, :] = 0
        self._lanes[:, :] = 0
        self._src[:] = -1
        for s in live:
            self._src[s] = src.get(s, -1)
            slot = self.slots[s]
            toks = plan[s]
            for j, lane in enumerate(lane_of.get(s, ())):
                chunk = toks[j * width:(j + 1) * width]
                self._lanes[lane, :3] = s, slot.pos + j * width, len(chunk)
                self._lanes[lane, 3:3 + len(chunk)] = chunk
            if s not in lane_of:    # a lane-fed slot idles in its group
                w = len(toks)
                self._tokens[s, :w] = toks
                self._positions[s, :w] = np.arange(slot.pos, slot.pos + w)
                self._active[s, :w] = True
        return plan, live, lane_of

    def _sent(self, plan: Dict[int, List[int]], live: List[int],
              lane_of: Dict[int, range], nxt, load) -> _Step:
        """The positional half of a step's commit, right behind its
        dispatch: count the step, move each live slot's position past a
        replay chunk or its one decoding token (a verify window's moves
        with what is accepted, in :meth:`_land`), snapshot a state where
        a chunk ended on its boundary, and keep for the landing which
        row of the choices is whose."""
        S, W = self.num_slots, self.window
        with self._cv:
            self._steps += 1
            self._active_steps_sum += len(live)
            self._counters["prefill_lane_steps"] += bool(lane_of)
            self._counters["tokens_fed"] += sum(len(plan[s]) for s in live)
            if self._state is not None:
                self._counters["state_rows_stepped"] += len(live)
        fed: Dict[int, _Fed] = {}
        for s in live:
            slot = self.slots[s]
            toks = plan[s]
            w = len(toks)
            pos = slot.pos
            # one compact flight record per slot-step: the "each decode
            # step" link of the request's trace chain — a postmortem
            # bundle reconstructs the request's whole schedule from
            # these by trace_id (tests/test_flight.py acceptance)
            FLIGHT.record("mark", "engine/slot_step",
                          trace_id=slot.req.trace_id,
                          engine_step=self._steps, slot=s, pos=pos,
                          width=w)
            # row j reads the pos + j + 1 tokens cached up to itself
            cache_read = w * pos + w * (w + 1) // 2
            chunk = pos < len(slot.replay) - 1
            # the slot group's choices lie row by row, then a lane's each
            # (the plain program's [S, W] read flat is the same rows)
            row = s * W
            with self._cv:
                self._cache_tokens_read += cache_read
                if chunk:
                    # replay chunk: all rows teacher-forced; the last row
                    # commits one token iff it reached the replay tail
                    n_prefill = min(w, len(slot.replay) - 1 - pos)
                    c = self._counters
                    c["prefill_tokens"] += n_prefill
                    if s in lane_of:
                        c["prefill_lane_tokens"] += n_prefill
                        c["prefill_lane_cache_tokens_read"] += cache_read
            if chunk:
                slot.pos = pos + w
                if slot.pos == len(slot.replay) // self.page_size \
                        * self.page_size:
                    self._snapshot(s, slot)
                if slot.pos != len(slot.replay):
                    row = None
                elif s in lane_of:
                    row = S * W + lane_of[s][-1]
                else:
                    row += w - 1
            elif w == 1:
                slot.pos = pos + 1
            fed[s] = _Fed(s, slot, pos, toks, chunk, row)
        return _Step(self._steps, nxt, load, fed, lanes=bool(lane_of))

    def _land(self, rec: _Step,
              dev: Optional[contextlib.ExitStack] = None) -> bool:
        """The second half of a step: the ONE host sync, then the values:
        each live slot's tokens appended and stamped, a verify window's
        acceptance, EOS and ``max_new``, ``_finish``, an expert model's
        load sums. A request that settled while the step was in flight
        (cancelled, expired, closed) is skipped: its token is dropped. A
        slot that stops on EOS here may already be fed in the step now in
        flight; that row is dead weight (never appended, stamped or
        delivered; its one cache row lies past the sequence's length on a
        page the slot then held). ``dev`` holds the open
        ``serving/decode_step`` span of the caller's turn and is closed
        after the sync (none given: the span is the sync's own). False
        iff the step failed (everything in flight settled typed)."""
        if rec.landed:
            return True
        rec.landed = True
        if self._in_flight is rec:
            self._in_flight = None
        with obs_context.bind(step=rec.step):
            gc_ns = GC_WATCH.pause_ns
            try:
                with dev if dev is not None \
                        else stat_timer("serving/decode_step"), \
                        self._phase("serving/sync", "host_sync_ns"):
                    waited = not self._ready(rec)
                    # an expert model's two load sums come with the tokens
                    if rec.load is None:
                        nxt, load = np.asarray(rec.nxt), None
                    else:
                        import jax
                        nxt, load = jax.device_get((rec.nxt, rec.load))
            # ptlint: disable=R7(serving boundary — in-flight requests settle typed and the pools rebuild; the engine thread must never die)
            except Exception as e:
                self._recover_from_step_failure(e)
                return False
            t = time.perf_counter_ns()
            self._look("sync", t)
            # the sync's end is where rec ended on the device unless a
            # pause held its return: a collection inside it, or one long
            # enough that the step behind rec has finished too
            behind = self._in_flight
            self._timed(rec, waited and GC_WATCH.pause_ns == gc_ns
                        and (behind is None or behind.done_at is None), t)
            with self._phase("serving/commit", "host_commit_ns"):
                self._commit(rec, nxt.reshape(-1))
                if load is not None:
                    with self._cv:
                        c = self._counters
                        c["expert_assignments_held"] += int(load[0])
                        c["expert_hits_held"] += int(load[1])
                        c["expert_layer_steps"] += self.paged.n_expert_layers
            self._look("commit")
            return True

    def _commit(self, rec: _Step, nxt) -> None:
        """The value half of a step's commit, after the sync: ``nxt`` is
        the step's choices read flat (:meth:`_sent` kept whose row is
        which). Commit each live slot's tokens, finish what is done."""
        t_after = self._clock()
        if PROFILER.enabled:
            PROFILER.on_step("decode")
        committed = 0
        for fed in rec.fed.values():
            s, slot, toks = fed.s, fed.slot, fed.toks
            req = slot.req
            if self.slots[s] is not slot or req.done.is_set():
                continue                # settled while in flight
            w = len(toks)
            if fed.chunk:
                if fed.row is None:
                    continue
                commits = [int(nxt[fed.row])]
            else:
                # speculative verify: outs[j] is the target's choice
                # after feeding tokens 0..j. Proposal j (toks[j+1]) is
                # accepted iff it IS that choice; the first rejection
                # ends the run and its row becomes dead weight the
                # kv_len mask never reads.
                m = w - 1
                outs = [int(nxt[fed.row + j]) for j in range(w)]
                commits = [outs[0]]
                a = 0
                while a < m and toks[a + 1] == commits[-1]:
                    commits.append(outs[a + 1])
                    a += 1
                if m:
                    with self._cv:
                        self._counters["spec_proposed_tokens"] += m
                        self._counters["spec_accepted_tokens"] += a
                    _SPEC_PROPOSED.inc(m)
                    if a:
                        _SPEC_ACCEPTED.inc(a)
                    req.accepted_tokens += a
                seq_before = len(req.prompt) + len(req.tokens)
                slot.draft_pos = min(slot.draft_pos, seq_before + a)
            done = False
            n_commit = 0
            with self._cv:
                if req.first_token_at is None:
                    req.first_token_at = t_after
                    self._ttft.append(t_after - req.submitted_at)
                dt = (t_after - slot.last_token_t) \
                    if slot.last_token_t is not None else None
                slot.last_token_t = t_after
                for tok in commits:
                    req.token_times.append(t_after)   # stamp first:
                    req.tokens.append(tok)  # a reader never lacks one
                    slot.last_tok = tok
                    n_commit += 1
                    self._counters["tokens_out"] += 1
                    if (req.eos_id is not None and tok == req.eos_id) \
                            or req.num_generated >= req.max_new:
                        done = True
                        break
                if dt is not None:
                    for _ in range(n_commit):
                        self._lat.append(dt / n_commit)
            committed += n_commit
            # a slot the step in flight already feeds stands one past
            # what is committed here (its pending token, on the device)
            ahead = slot.pos > fed.pos + w
            if not fed.chunk:
                # keep only the fed rows that match the committed
                # sequence: pending token + (n_commit - 1) accepted
                if w > 1:
                    slot.pos = fed.pos + n_commit
                slot.draft_pos = min(slot.draft_pos, fed.pos + n_commit)
            if done:
                if ahead:
                    # EOS, learned a step late: the row in flight is dead
                    # weight. The slot ends where the committed sequence
                    # does; a state row has moved past it
                    slot.pos -= 1
                self._finish(s, "done", snapshot=not ahead)
        if committed:
            global_counters.bump("serving/decode_tokens", committed)

    def _recover_from_step_failure(self, exc: Exception) -> None:
        """A failed dispatch may have consumed the (donated) pools:
        settle everything in flight with a typed error, then rebuild
        pools + free-list + prefix index + draft caches so fresh
        traffic can still be served. A step still in flight goes with
        the one that failed (its pools were donated to it): its requests
        hold their slots and settle here, and its record is given up."""
        self._give_up_in_flight()
        in_flight = [self.slots[s].req.trace_id
                     for s in range(self.num_slots)
                     if self.slots[s] is not None]
        with self._cv:
            self._counters["step_failures"] += 1
            waiting_ids = [r.trace_id for r in self._waiting]
        err = ServingError(f"decode step failed: {exc}")
        for s in range(self.num_slots):
            if self.slots[s] is not None:
                self._finish(s, "failed", err)
        with self._cv:
            while self._waiting:
                req = self._waiting.popleft()
                FLIGHT.record("mark", "engine/settle",
                              trace_id=req.trace_id, state="failed",
                              where="waiting")
                self._settle(req, "failed", err)
        self.k_pool, self.v_pool = self.paged.init_pools()
        self.pool = PagePool(self.pool.num_pages)
        if self.prefix is not None:
            # the trie indexed pages of the DEAD pool: forget them all
            # and repoint at the rebuilt allocator
            self.prefix.reset()
            self.prefix.pool = self.pool
        if self.spill is not None:
            # host entries were carved from the dead trie — NEVER
            # restore across a rebuild (torn-state resurrection)
            self._counters["kv_spill_cleared"] += self.spill.clear()
        if self.draft is not None:
            self._draft_kc, self._draft_vc = self.draft.init_caches()
        self._tables[:, :] = 0
        self._active[:, :] = False
        # journaled AFTER the typed settles so the auto-dumped bundle
        # (obs/flight.py trigger) contains each victim's COMPLETE chain
        # — submit → admit → every slot_step → settle(failed) — plus
        # this record naming the in-flight trace ids at fault time
        journal_emit("engine", "step_failure", error=repr(exc)[:400],
                     trace_ids=in_flight, waiting_trace_ids=waiting_ids,
                     engine_step=self._steps)

    def _has_work(self) -> bool:
        return any(s is not None for s in self.slots) or \
            bool(self._waiting)

    def run(self, timeout: float = 120.0) -> None:
        """Synchronous drive: step until every submitted request has
        settled (the deterministic test/bench mode)."""
        deadline = time.monotonic() + timeout
        while self._has_work():
            self.step()
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"engine did not drain within {timeout}s "
                    f"({self.stats()})")

    # ------------------------------------------------------------ lifecycle
    def warmup(self) -> dict:
        """Resolve the decode executables NOW, before any request is
        admitted — the warm-start plane's engine hook (docs/
        robustness.md "Warm start & artifact integrity").

        Dispatches one all-inactive step through each of the target's
        two programs, the plain one and the lane program (and the draft,
        when speculating): inactive slots and unfed lanes write only the
        reserved null page / null row, so pools are semantically
        untouched, and the dispatch shapes are exactly the serving
        shapes — the executables resolved here ARE the ones every later
        step reuses. With a warm artifact store the whole call is
        zero-compile (deserialized executables trace nothing); cold,
        it pays the compiles up front and backfills the store, so
        first-token latency never pays them. Returns resolver stats."""
        from paddle_tpu.artifacts import EXECUTABLES
        S, W = self.num_slots, self.window
        z = np.zeros((S, W), np.int32)
        inactive = np.zeros((S, W), np.bool_)
        self._lanes[:, :] = 0
        for lanes in (None, self._lanes)[:1 + (self._lane_shape[0] > 0)]:
            nxt, self.k_pool, self.v_pool = self.paged.step(
                self.k_pool, self.v_pool, z, z, self._tables, inactive,
                lanes=lanes)
            # the feed that hands this program's choices to the next step
            self.paged.feed(nxt, self._src, z)
        if self.draft is not None:
            _, self._draft_kc, self._draft_vc = self.draft.step(
                self._draft_kc, self._draft_vc, z, z, inactive)
        if self._state is not None:     # the row copy: zero row onto itself
            self._copy_state(self._state.zero_row, self._state.zero_row)
        return dict(EXECUTABLES.stats(), warm_start=self.warm_start)

    def start(self) -> "DecodeEngine":
        watch_gc()
        with self._cv:
            if self._thread is not None:
                return self
            self._stopping = False
            self._accepting = True
            t = threading.Thread(target=self._loop,
                                 name="pt-serve-decode", daemon=True)
            self._thread = t
            t.start()
        return self

    def _loop(self) -> None:
        """The engine's thread: one step in flight. Each turn launches
        step N+1 while step N runs on the device, then lands N (waits
        for it, commits its values) and goes round, so the device finds
        its next step queued and the host's cycle hides behind the
        device's. The depth is one, fixed."""
        while True:
            with self._cv:
                if self._close_now:
                    break
                if self._in_flight is None and not self._has_work():
                    if self._stopping:
                        return
                    # one span and one counter update per idle stretch
                    with self._phase("serving/idle", "host_idle_ns"):
                        while not (self._has_work() or self._stopping
                                   or self._close_now):
                            self._cv.wait(0.05)
                    continue
            with obs_context.bind(step=self._steps + 1), \
                    self._phase("serving/step"), \
                    contextlib.ExitStack() as dev:
                older = self._in_flight
                self._launch(dev)
                if older is not None:
                    self._land(older, dev)
        self._close_all()

    def _close_all(self) -> None:
        """Settle everything in flight with ServerClosed and return
        every page — runs on the STEPPING thread, so it never races a
        dispatch. A step still in flight is given up: its tokens are
        dropped with the requests it ran for."""
        self._give_up_in_flight()
        for s in range(self.num_slots):
            if self.slots[s] is not None:
                self._finish(s, "failed", ServerClosed(
                    "engine shut down mid-generation"))
        with self._cv:
            while self._waiting:
                req = self._waiting.popleft()
                self._counters["closed"] += 1
                self._settle(req, "failed", ServerClosed(
                    "engine shut down before this request ran"))

    def drain_admission(self) -> None:
        """Deploy-drain: stop ADMITTING (submit raises ServerClosed)
        while the loop keeps stepping everything already in flight —
        the fleet router's POST /admin/drain leg
        (docs/robustness.md "Serving fleet"). Reversible via
        :meth:`resume_admission`; full stop stays :meth:`shutdown`."""
        with self._cv:
            self._accepting = False

    def resume_admission(self) -> None:
        """Re-open admission after :meth:`drain_admission` (no-op on a
        stopping engine)."""
        with self._cv:
            if not self._stopping:
                self._accepting = True
                self._cv.notify_all()

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = 30.0) -> None:
        """Stop accepting. With ``drain`` in-flight generation
        completes; without it everything settles ServerClosed and the
        pages return to the pool immediately."""
        with self._cv:
            self._accepting = False
            self._close_now = self._close_now or not drain
            self._stopping = True
            self._cv.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout)
            with self._cv:
                self._thread = None
        elif drain:
            self.run(timeout=timeout if timeout is not None else 120.0)
        else:
            self._close_all()

    # ------------------------------------------------------------ snapshots
    @staticmethod
    def _percentile(vals: List[float], q: float) -> float:
        if not vals:
            return 0.0
        s = sorted(vals)
        idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
        return s[idx]

    def page_accounting(self) -> dict:
        """Pool truth vs slot + trie holdings — the chaos suite's
        no-leak assertion reads ``leaked`` (== 0 always) and
        cross-checks ``refs_total`` == ``held_by_slots`` +
        ``held_by_trie`` (zero refcount underflows). With a spill
        store the dict grows the SECOND tier (``spilled``,
        ``spill_capacity``, ...): the extended invariant
        (tests/test_serving_faults.py ``assert_pool_balanced``) also
        proves host-tier conservation — spills in == restores +
        integrity drops + LRU drops + recovery clears + still-resident
        entries."""
        acc = self.pool.accounting()
        acc["held_by_slots"] = sum(
            len(s.pages) for s in self.slots if s is not None)
        acc["held_by_trie"] = self.prefix.page_count() \
            if self.prefix is not None else 0
        if self._state is not None and self.prefix is not None:
            # snapshot rows are a pool of their own: one that neither the
            # free rows nor a node holds is leaked like a page
            snap = self.prefix.snapshot_accounting()
            acc["leaked"] += snap.pop("snapshot_rows_leaked")
            acc.update(snap)
        if self.spill is not None:
            acc.update(self.spill.accounting())
            acc["spill_cleared"] = self._counters["kv_spill_cleared"]
        else:
            acc["spilled"] = 0
            acc["spill_capacity"] = 0
        return acc

    def stats(self) -> dict:
        with self._cv:
            counters = dict(self._counters)
            lat = list(self._lat)
            ttft = list(self._ttft)
            waiting = len(self._waiting)
            steps = self._steps
            active_sum = self._active_steps_sum
            cache_read = self._cache_tokens_read
        active = sum(1 for s in self.slots if s is not None)
        util = (active_sum / (steps * self.num_slots)) if steps else 0.0
        shared = self.pool.shared_pages
        leaked = self.pool.accounting()["leaked"]
        _PREFIX_SHARED.set(shared)
        spilled_now = len(self.spill) if self.spill is not None else 0
        spill_cap = self.spill.capacity if self.spill is not None else 0
        _SPILLED_NOW.set(spilled_now)
        snaps = self.prefix.snapshot_accounting()["snapshot_rows_held"] \
            if self.prefix is not None else 0
        out = dict(counters)
        out.update({
            "state_snapshots_evicted": self.prefix.snapshots_dropped
            if self.prefix is not None else 0,
            "slots": self.num_slots,
            "active_slots": active,
            "waiting": waiting,
            "slot_utilization": round(util, 4),
            "kv_pages_total": self.pool.usable,
            "kv_pages_free": self.pool.free_pages,
            "kv_pages_used": self.pool.used_pages,
            "kv_pages_shared": shared,
            # the no-leak invariant, scrapeable: survivors of a chaos
            # storm must show 0 here (tests/test_fleet_faults.py reads
            # it over GET /stats)
            "kv_pages_leaked": leaked,
            # trie-held pages _admit would evict on demand: a router
            # judging this replica's headroom off the free list alone
            # would livelock after a prefix-heavy burst (the trie only
            # yields pages under admission pressure, which a gated
            # router never applies)
            "kv_pages_reclaimable": self.prefix.reclaimable_pages()
            if self.prefix is not None else 0,
            "kv_page_high_water": self.pool.high_water,
            # the second tier: current host-resident pages, capacity,
            # and the lossless headroom the router counts toward this
            # replica's admission (fleet/balance.py)
            "kv_pages_spilled_now": spilled_now,
            "kv_spill_capacity": spill_cap,
            "kv_spill_headroom": max(0, spill_cap - spilled_now),
            "kv_quant": self.kv_quant or "none",
            "kv_quant_bits": 8 if self.kv_quant == "int8" else
            int(np.dtype(getattr(self.paged, "dtype", "float32"))
                .itemsize) * 8,
            "page_size": self.page_size,
            "window": self.window,
            "prefill_lanes": self._lane_shape[0],
            "prefill_lane_width": self._lane_shape[1],
            "spec_k": self.spec_k,
            "prefix_nodes": self.prefix.page_count()
            if self.prefix is not None else 0,
            "steps": steps,
            "active_slot_steps": active_sum,
            "cache_tokens_read": cache_read,
            # state rows in use: the occupied slots' and the snapshots'
            "state_rows_live": active + snaps
            if self._state is not None else 0,
            "token_latency_p50_ms":
                round(self._percentile(lat, 0.50) * 1e3, 3),
            "token_latency_p99_ms":
                round(self._percentile(lat, 0.99) * 1e3, 3),
            "ttft_p50_ms": round(self._percentile(ttft, 0.50) * 1e3, 3),
        })
        # the collector's pauses: PROCESS-wide (utils.stats.watch_gc,
        # installed by start()), not this engine's alone
        for k in ("gc_collections", "gc_gen2_collections", "gc_pause_ns"):
            out[k] = global_counters.value(k)
        return out
