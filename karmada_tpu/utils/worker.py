"""Reconcile runtime: rate-limited work queues + a deterministic driver.

Ref: pkg/util/worker.go:33-140 (util.AsyncWorker — workqueue + reconcile
loop). The TPU build keeps the same enqueue/reconcile contract but adds a
deterministic cooperative mode (``Runtime.run_until_settled``) so the whole
control plane can be exercised in-process without sleeping threads — the
pattern SURVEY.md section 4.3 calls "distributed-without-a-cluster".
"""

from __future__ import annotations

import collections
import heapq
import itertools
import logging
import time
from typing import Callable, Hashable, Optional

log = logging.getLogger("karmada_tpu")

# Reconcile results
DONE = "done"
REQUEUE = "requeue"


class WriteCount:
    """A plain count of the writes made to a plane's state, shared by all
    that holds a part of it (the store, the member clients) and read by the
    workers to tell a reconcile that changed nothing. Bumped and read
    without a lock: it is a statistic, and a lost update miscounts a key."""

    __slots__ = ("n",)

    def __init__(self) -> None:
        self.n = 0


class Worker:
    """A named reconcile queue. ``reconcile(key)`` returns DONE or REQUEUE
    (or raises — treated as REQUEUE with backoff count).

    Two requeue disciplines (pkg/util/worker.go wraps a rate-limiting
    workqueue — DefaultControllerRateLimiter: per-item exponential backoff
    5ms..1000s):

    - cooperative (default): REQUEUE re-enqueues immediately and drops the
      key after MAX_RETRIES — deterministic, for ``run_until_settled``
      test drivers where wall-clock delays would just burn the step budget.
    - wall-clock (``runtime.realtime = True``, the serve deployments):
      REQUEUE parks the key for ``backoff_base * 2^(retries-1)`` seconds
      (capped at ``backoff_max``) and retries indefinitely — a persistently
      failing key costs one reconcile per backoff window instead of 16
      hot-loop attempts followed by a permanent drop.

    Ownership sharding (ISSUE 11): with ``shard_fn`` set, keys route to
    per-ownership-token queues (the binding/detector workers shard by
    namespace) drained round-robin, and a BATCH drain holds keys of one
    token only — so one namespace's storm (or a poisoned key's bisect
    fan-out, or a parked batch flush) never head-of-line-blocks another
    namespace's drain, and each batched write set stays within one
    ownership domain.

    Work counts (ISSUE 25): plain integers, added up where the work happens
    and handed out once a drain by ``take_counts`` — keys enqueued, keys
    reconciled, and keys that were a NO-OP. A key is a no-op when its reconcile finished (DONE) and wrote
    nothing: the runtime's ``write_count`` did not move across it. A
    batch reconciler says so itself with ``note_noops`` (the scheduler's
    gate turns keys away before the engine) or runs its loop through
    ``reconcile_each`` (the controllers that buffer a drain's writes).
    Where neither can tell — a batch that wrote something and reported
    nothing — the drain carries no no-op count at all, never a guess.
    Enqueues are not counted call by call (one more attribute store in
    ``enqueue`` cost the plane cell 2.5% of its wave, measured on the chip's
    host): the keys a drain was fed are the keys popped plus the growth of
    the queue, and calls that found their key already queued go uncounted.
    """

    MAX_RETRIES = 16

    def __init__(
        self,
        name: str,
        reconcile: Callable[[Hashable], Optional[str]],
        *,
        reconcile_batch: Optional[
            Callable[[list[Hashable]], dict[Hashable, Optional[str]]]
        ] = None,
        batch_size: int = 1024,
        runtime: Optional["Runtime"] = None,
        backoff_base: float = 0.005,
        backoff_max: float = 300.0,
        clock: Callable[[], float] = time.monotonic,
        shard_fn: Optional[Callable[[Hashable], Hashable]] = None,
    ):
        self.name = name
        self.reconcile = reconcile
        # optional vectorized drain: given up to batch_size queued keys,
        # returns per-key results (missing keys count as DONE). Lets batch
        # engines (the tensor scheduler) amortize one kernel pass over every
        # queued item instead of paying per-key packing/dispatch.
        self.reconcile_batch = reconcile_batch
        self.batch_size = batch_size
        self.runtime = runtime
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.clock = clock
        # key -> ownership token; tokens materialize shard queues lazily
        # (a namespace that never enqueues costs nothing)
        self.shard_fn = shard_fn
        self._queue: collections.deque[Hashable] = collections.deque()
        self._shards: dict[Hashable, collections.deque] = {}
        self._shard_rr: collections.deque = collections.deque()
        self._queued: set[Hashable] = set()
        self._retries: collections.Counter = collections.Counter()
        self._delayed: list[tuple] = []  # (not_before, seq, key) heap
        #: live parked entry per key: key -> (not_before, seq). Heap
        #: entries not matching this map are stale and skipped on promote
        #: (client-go's delaying queue keeps ONE ready-time per item —
        #: the earliest; without dedup a watch-triggered direct enqueue
        #: would leave a stale long-backoff entry to fire a spurious
        #: reconcile later)
        self._parked: dict[Hashable, tuple] = {}
        self._seq = itertools.count()
        # work counts since the last take_counts() (see the class docstring)
        self.keys = 0
        self.noops = 0
        self._noops_known = True
        self._noted: Optional[int] = None  # note_noops of the call in flight
        self._queued_at_take = 0

    def enqueue(self, key: Hashable) -> None:
        # a direct enqueue supersedes any parked retry of the same key
        self._parked.pop(key, None)
        if key in self._queued:
            return
        self._queued.add(key)
        if self.shard_fn is None:
            self._queue.append(key)
            return
        token = self.shard_fn(key)
        q = self._shards.get(token)
        if q is None:
            q = self._shards[token] = collections.deque()
            self._shard_rr.append(token)
        q.append(key)

    def _pop_batch(self, limit: int) -> list:
        """Pop up to ``limit`` queued keys. Sharded workers drain from ONE
        ownership token per call (round-robin across tokens), so a batch
        never mixes ownership domains."""
        keys: list = []
        if self.shard_fn is None:
            while self._queue and len(keys) < limit:
                k = self._queue.popleft()
                self._queued.discard(k)
                keys.append(k)
            return keys
        while self._shard_rr and not keys:
            token = self._shard_rr.popleft()
            q = self._shards.get(token)
            if not q:
                self._shards.pop(token, None)
                continue
            while q and len(keys) < limit:
                k = q.popleft()
                self._queued.discard(k)
                keys.append(k)
            if q:
                self._shard_rr.append(token)  # remainder: back of rotation
            else:
                self._shards.pop(token, None)
        return keys

    def enqueue_after(self, key: Hashable, delay: float) -> None:
        """Park ``key`` until ``delay`` seconds from now (workqueue
        AddAfter): the EARLIEST pending ready-time per key wins, and a
        direct enqueue while parked wins outright (retries sooner)."""
        due = self.clock() + delay
        live = self._parked.get(key)
        if live is not None and live[0] <= due:
            return
        entry = (due, next(self._seq), key)
        self._parked[key] = (due, entry[1])
        heapq.heappush(self._delayed, entry)

    def _promote_due(self) -> None:
        now = self.clock()
        while self._delayed and self._delayed[0][0] <= now:
            due, seq, key = heapq.heappop(self._delayed)
            if self._parked.get(key) != (due, seq):
                continue  # superseded by a direct enqueue or earlier park
            del self._parked[key]
            self.enqueue(key)

    def __len__(self) -> int:
        # _queued mirrors the queued key set exactly (enqueue dedups on
        # it, every pop discards from it) across both queue layouts
        return len(self._queued)

    @property
    def delayed(self) -> int:
        """Keys parked in a backoff window (not yet due)."""
        return len(self._parked)

    def next_due(self) -> Optional[float]:
        """Seconds until the earliest parked key is due (<= 0 if due now),
        or None when nothing is parked."""
        while self._delayed and (
            self._parked.get(self._delayed[0][2])
            != (self._delayed[0][0], self._delayed[0][1])
        ):
            heapq.heappop(self._delayed)  # drop stale heads lazily
        if not self._delayed:
            return None
        return self._delayed[0][0] - self.clock()

    def note_noops(self, n: int) -> None:
        """A reconciler's own count of the keys of the call in flight that
        needed nothing done. Takes the place of the worker's write-based
        reading for that call."""
        self._noted = (self._noted or 0) + n

    def take_counts(self) -> dict:
        """The work counted since the last call, and zero the counters:
        keys ``enqueued`` (those that FED this drain, whichever drain of
        another worker queued them), ``keys`` reconciled, and ``noop`` of
        them — left out where some call of the stretch could not tell."""
        queued = len(self._queued)
        out = {
            "keys": self.keys,
            # every accepted call put a key in the queue, every pop took one
            "enqueued": self.keys + queued - self._queued_at_take,
        }
        if self._noops_known:
            out["noop"] = self.noops
        self.keys = self.noops = 0
        self._noops_known = True
        self._queued_at_take = queued
        return out

    def reconcile_each(self, keys, reconcile, buffered) -> dict:
        """The loop of a batch reconciler that BUFFERS its writes and
        flushes them after the loop: reconcile every key, and note as
        no-ops those that finished with nothing buffered (``buffered()``,
        a count, did not move) and nothing written directly."""
        wc = self.runtime.write_count if self.runtime is not None else None
        if wc is None:
            return {k: reconcile(k) for k in keys}
        out: dict = {}
        noops = 0
        wrote, held = wc.n, buffered()
        for k in keys:
            out[k] = res = reconcile(k)
            if wc.n != wrote or buffered() != held:
                wrote, held = wc.n, buffered()
            elif res != REQUEUE:
                noops += 1
        self.note_noops(noops)
        return out

    def _count(self, n: int, requeued: int, wrote: Optional[bool]) -> None:
        """Add one reconcile call's ``n`` keys to the counts. ``wrote``:
        whether the runtime's write count moved across the call (None: no
        count to read)."""
        self.keys += n
        noted = self._noted
        if noted is not None:
            self._noted = None
            self.noops += min(noted, n)
        elif wrote is False and not requeued:
            self.noops += n
        elif n > 1 or wrote is None:
            self._noops_known = False
        # else: one key that wrote or asked to come back — useful work

    def process_one(self) -> bool:
        """Pop and reconcile one key (or one batch when a batch reconciler
        is installed and multiple keys are queued). Returns True if work was
        done."""
        if self._delayed:
            self._promote_due()
        if not self._queued:
            return False
        if self._noted is not None:
            self._noted = None  # left by a call made outside a drain
        wc = self.runtime.write_count if self.runtime is not None else None
        before = wc.n if wc is not None else None
        if self.reconcile_batch is not None and len(self._queued) > 1:
            keys = self._pop_batch(self.batch_size)
            results = self._drain_batch(keys)
            wrote = None if wc is None else wc.n != before
            requeued = 0
            for k in keys:
                requeued += self._finish(k, results.get(k, DONE))
            self._count(len(keys), requeued, wrote)
            return True
        popped = self._pop_batch(1)
        if not popped:
            return False
        key = popped[0]
        try:
            result = self.reconcile(key)
        except Exception:  # noqa: BLE001 — reconcile errors requeue, like workqueue
            log.exception("worker %s: reconcile %r failed", self.name, key)
            result = REQUEUE
        wrote = None if wc is None else wc.n != before
        self._count(1, self._finish(key, result), wrote)
        return True

    #: poisoned keys tolerated per drain before the failure is treated as
    #: systemic (whole engine down, not bad keys); each poisoned key costs
    #: ~log2(batch) failing sub-batch calls down its bisect path
    POISON_TOLERANCE = 4

    def _drain_batch(self, keys: list[Hashable]) -> dict[Hashable, Optional[str]]:
        """Run reconcile_batch with poisoned-key isolation.

        A batch-wide REQUEUE on exception would make every key in the batch
        burn retries together with the one bad key (all dropped together at
        MAX_RETRIES). Instead, bisect the failing batch: healthy halves stay
        batched, and only genuinely failing keys pay a retry. A failure
        budget caps the fan-out when the failure is systemic (every sub-call
        failing) so a batch-wide transient costs O(budget) calls and one
        logged traceback, not O(batch) of each."""
        results: dict[Hashable, Optional[str]] = {}
        failures = 0
        budget = self.POISON_TOLERANCE * max(1, len(keys).bit_length())

        def run(ks: list[Hashable]) -> None:
            nonlocal failures
            if failures > budget:
                for k in ks:
                    results[k] = REQUEUE
                return
            try:
                if len(ks) == 1:
                    results[ks[0]] = self.reconcile(ks[0])
                else:
                    results.update(self.reconcile_batch(ks))
                return
            except Exception:  # noqa: BLE001
                failures += 1
                if failures == 1:
                    log.exception(
                        "worker %s: batch reconcile failed; bisecting", self.name
                    )
                else:
                    log.error(
                        "worker %s: reconcile of %d key(s) failed (failure %d)",
                        self.name, len(ks), failures,
                    )
                if len(ks) == 1:
                    results[ks[0]] = REQUEUE
                    return
            mid = len(ks) // 2
            run(ks[:mid])
            run(ks[mid:])

        run(keys)
        return results

    def _finish(self, key: Hashable, result: Optional[str]) -> bool:
        """Settle one reconciled key; True where it asked to come back."""
        if result == REQUEUE:
            self._retries[key] += 1
            if self.runtime is not None and self.runtime.realtime:
                # exponent is capped: retries grow without bound in
                # realtime mode and 2**1025 overflows float conversion
                delay = min(
                    self.backoff_base
                    * (2 ** min(self._retries[key] - 1, 30)),
                    self.backoff_max,
                )
                self.enqueue_after(key, delay)
            elif self._retries[key] <= self.MAX_RETRIES:
                self.enqueue(key)
            else:
                log.error("worker %s: dropping %r after max retries", self.name, key)
                del self._retries[key]
            return True
        self._retries.pop(key, None)
        return False


class Runtime:
    """Holds all workers of a control plane and drives them cooperatively.

    ``run_until_settled`` round-robins workers until every queue is empty
    (i.e. the control plane reached a fixed point) or the step budget is hit.
    """

    def __init__(self) -> None:
        self.workers: list[Worker] = []
        self._tickers: list[Callable[[], None]] = []
        #: moves with every write a reconcile can make (the plane hands in
        #: the count its store and member clients share; None = nothing to
        #: read, and the workers count no write-based no-ops): the workers
        #: never learn what a store is
        self.write_count: Optional[WriteCount] = None
        #: wall-clock mode (serve deployments): failing keys back off
        #: exponentially instead of hot-looping; see Worker._finish
        self.realtime = False

    def new_worker(self, name: str, reconcile, **kw) -> Worker:
        w = Worker(name, reconcile, runtime=self, **kw)
        self.workers.append(w)
        return w

    def next_due(self) -> Optional[float]:
        """Seconds until the earliest backed-off key anywhere is due, or
        None — the serve loop's sleep bound."""
        dues = [d for w in self.workers if (d := w.next_due()) is not None]
        return min(dues) if dues else None

    def add_ticker(self, fn: Callable[[], None]) -> None:
        """Periodic function run at the start of each run_until_settled call
        (cluster status refresh, descheduler sweep, etc. — the analogue of
        wait.Until loops)."""
        self._tickers.append(fn)

    def tick(self) -> None:
        for fn in self._tickers:
            fn()

    def pending(self) -> int:
        return sum(len(w) for w in self.workers)

    # called every HEARTBEAT_EVERY drained items mid-settle (None = off).
    # Returning False aborts the drain with work still queued — the seam a
    # leader-elected plane uses to renew its Lease during a storm settle
    # and to STOP reconciling the moment it is deposed (client-go renews on
    # a background goroutine; this runtime is cooperative, so renewal must
    # ride the drain loop itself)
    heartbeat = None
    HEARTBEAT_EVERY = 256

    def run_until_settled(self, max_steps: int = 100_000, *, tick: bool = True) -> int:
        """Process queued work until quiescent. Returns steps executed.

        Tickers run once at the start (not per pass — a ticker that always
        enqueues would never settle); wall-clock periodicity comes from the
        caller invoking this repeatedly, as a real deployment's main loop
        does. ``heartbeat`` (if set) is invoked every HEARTBEAT_EVERY items
        so long drains cannot starve time-critical duties; a False return
        aborts the drain (remaining keys stay queued for the next call).

        Wave tracing: a settle with queued work is the unit the wave tree
        hangs off — a ``settle`` root span wraps the drain, one
        ``controller.<worker>`` child span per contiguous worker drain
        (NOT per key: a 100k-binding storm is a handful of spans, not
        100k), and the wave closes at quiescence so the next trigger
        starts a fresh wave. Per-worker drain counts feed the
        karmada_tpu_worker_* metric families once per drain — never per
        key, the drain loop is the storm hot path."""
        if tick:
            self.tick()
        if self.pending() == 0:
            due = self.next_due()
            if due is None or due > 0:
                return 0  # quiescent (no queued keys, no due-parked keys)
        from .metrics import (
            settle_seconds,
            worker_noop_reconciles,
            worker_queue_depth,
            worker_reconciles,
        )
        from .tracing import tracer

        tracer.ensure_wave("settle")
        steps = 0
        next_beat = self.HEARTBEAT_EVERY
        aborted = False
        with tracer.span("settle") as root:
            while steps < max_steps and not aborted:
                progressed = False
                for w in self.workers:
                    drained = 0
                    wc = self.write_count
                    wrote0 = wc.n if wc is not None else 0
                    # the whole drain — including its FIRST item — runs
                    # inside the controller span; an idle poll discards
                    # the span so quiescent workers leave no trace
                    with tracer.span(f"controller.{w.name}") as sp:
                        while (
                            steps < max_steps
                            and not aborted
                            and w.process_one()
                        ):
                            steps += 1
                            drained += 1
                            if (
                                self.heartbeat is not None
                                and steps >= next_beat
                            ):
                                next_beat = steps + self.HEARTBEAT_EVERY
                                if self.heartbeat() is False:
                                    aborted = True
                        sp.attrs["items"] = drained
                        if not drained:
                            sp.attrs["_discard"] = True
                        else:
                            # the drain's work counts ride its span: what a
                            # wave did at this boundary, and how much of it
                            # was for nothing (ISSUE 25)
                            counts = w.take_counts()
                            sp.attrs.update(counts)
                            if wc is not None:
                                sp.attrs["writes"] = wc.n - wrote0
                    if not drained:
                        continue
                    progressed = True
                    worker_reconciles.inc(drained, worker=w.name)
                    if counts.get("noop"):
                        worker_noop_reconciles.inc(
                            counts["noop"], worker=w.name
                        )
                    worker_queue_depth.set(len(w), worker=w.name)
                    if aborted or steps >= max_steps:
                        break
                if not progressed:
                    break
            root.attrs["steps"] = steps
        settle_seconds.observe(root.duration)
        if self.pending() == 0:
            tracer.end_wave()
        return steps
