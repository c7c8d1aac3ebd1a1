"""In-memory API store with watch bus — the control-plane state hub.

Plays the role the kube-apiserver + informers play in the reference: typed
buckets keyed by (kind, namespace/name), resource-version bumping, watch
handlers, finalizer-aware deletion. Controllers subscribe and reconcile; the
whole control plane can be driven deterministically with
``Runtime.run_until_settled`` (karmada_tpu.utils.worker).

Ref analogues: client-go informers / fedinformer managers (pkg/util/fedinformer)
and the apiserver REST semantics the reference assumes.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional

from ..api.core import ObjectMeta, new_uid
from .worker import WriteCount

ADDED = "Added"
MODIFIED = "Modified"
DELETED = "Deleted"


class ConflictError(RuntimeError):
    """Optimistic-concurrency precondition failed (the apiserver's 409):
    the object's resource_version moved under the caller. Re-read and
    retry, or give up the claim (leader election's loss signal)."""


@dataclass(frozen=True)
class Event:
    type: str  # Added | Modified | Deleted
    kind: str
    key: str  # namespace/name or name
    obj: Any


WatchHandler = Callable[[Event], None]


def obj_key(obj: Any) -> str:
    meta: ObjectMeta = obj.meta
    return meta.namespaced_name


def obj_kind(obj: Any) -> str:
    return type(obj).KIND if hasattr(type(obj), "KIND") else type(obj).__name__


def _move_generation(obj: Any, existing: Any, status_only: bool) -> None:
    """A write over ``existing`` of a kind whose generation the store owns
    (``STORE_GENERATION`` on the class: Work). Only a write that says it is
    status-only AND hands in the stored object itself leaves the generation
    where it is: another object under the key may carry any spec."""
    if getattr(type(obj), "STORE_GENERATION", False) and not (
        status_only and existing is obj
    ):
        obj.meta.generation = existing.meta.generation + 1


class Store:
    """Typed object store. Mutations are thread-safe; watch handlers run
    synchronously on the mutating thread, outside the lock (so handlers may
    re-enter the store). Cross-thread event *ordering* is therefore not
    guaranteed — the deterministic control-plane runtime (utils.worker) is
    single-threaded, which is the supported concurrency model; multi-threaded
    callers must tolerate reordered events, as with real informers."""

    def __init__(
        self,
        admission: Optional[Callable[[str, Any], None]] = None,
        delete_admission: Optional[Callable[[str, Any], None]] = None,
    ) -> None:
        self._lock = threading.RLock()
        self._buckets: dict[str, dict[str, Any]] = {}
        self._watchers: dict[str, list[WatchHandler]] = {}
        self._all_watchers: list[WatchHandler] = []
        self._rv = 0
        #: every mutation bumps it (a hard delete too, which moves no
        #: resource version); the plane shares it with its member clients
        #: and its reconcile runtime reads it (utils.worker.WriteCount)
        self.write_count = WriteCount()
        # admission(kind, obj) raises to reject an apply (webhook seam);
        # delete_admission likewise guards Delete operations
        self._admission = admission
        self._delete_admission = delete_admission

    # -- mutation ----------------------------------------------------------

    @property
    def rv(self) -> int:
        """Current resource-version counter (public read for change-gated
        periodic checkpoints and diagnostics)."""
        with self._lock:
            return self._rv

    def advance_rv(self, rv: int) -> None:
        """Advance the resource-version counter to at least ``rv - 1`` so the
        NEXT apply stamps ``rv``. Public seam for replicas mirroring a
        primary's version stream (bus StoreReplica): the replica aligns the
        counter before each replayed apply so its objects carry the
        primary's rvs without reaching into Store internals."""
        with self._lock:
            self._rv = max(self._rv, rv - 1)

    def apply(
        self,
        obj: Any,
        *,
        expected_rv: Optional[int] = None,
        status_only: bool = False,
    ) -> Any:
        """Create-or-update. Bumps resource_version.

        ``meta.generation`` belongs to the writers (the detector, the
        rebalancer, the CLI move it with the spec; ``bump_generation``), but
        for a kind that sets ``STORE_GENERATION`` (Work) it belongs to the
        store, as it does to the apiserver upstream: every write over an
        existing object moves it, but a write that passes ``status_only``
        (the status-subresource analogue: execution's conditions,
        work-status's manifest statuses) with the stored object itself.
        Any writer that does not say so — a fresh object applied over the
        key, a replica's replay, a facade that cannot carry the word —
        reads as a spec write. The readers are the Work watchers that do
        spec-derived work (ExecutionController, WorkIndex): a Modified
        event at the generation they acted on is a status write.

        ``expected_rv`` is the apiserver's optimistic-concurrency
        precondition: the write succeeds only if the CURRENT object's
        resource_version equals it (0 = the object must not exist yet);
        otherwise ConflictError (HTTP 409). The compare-and-swap leader
        election and controllers racing on shared objects build on this."""
        kind = obj_kind(obj)
        key = obj_key(obj)
        if self._admission is not None:
            self._admission(kind, obj)
        with self._lock:
            bucket = self._buckets.setdefault(kind, {})
            existing = bucket.get(key)
            if expected_rv is not None:
                current_rv = (
                    existing.meta.resource_version
                    if existing is not None
                    else 0
                )
                if current_rv != expected_rv:
                    raise ConflictError(
                        f"{kind} {key!r}: resource_version is "
                        f"{current_rv}, precondition {expected_rv}"
                    )
            self._rv += 1
            self.write_count.n += 1
            obj.meta.resource_version = self._rv
            if existing is not None:
                _move_generation(obj, existing, status_only)
            if not obj.meta.uid:
                obj.meta.uid = existing.meta.uid if existing else new_uid()
            if existing is None and not obj.meta.creation_timestamp:
                import time

                obj.meta.creation_timestamp = time.time()
            bucket[key] = obj
            event = Event(MODIFIED if existing is not None else ADDED, kind, key, obj)
        self._deliver(event)
        return obj

    def apply_many(self, objs: list, *, status_only: bool = False) -> list:
        """Batched create-or-update for INDEPENDENT objects: admission runs
        per object (against pre-batch state — use only for sweeps whose
        objects don't admit against each other, like a storm writeback
        over distinct bindings), then one lock acquisition commits every
        ACCEPTED mutation, then one delivery sweep fans the events out.
        A 100k-binding writeback is 100k ``apply`` calls otherwise —
        per-call lock churn and bookkeeping were ~30% of the measured
        whole-plane wave.

        Admission rejections do NOT abort the batch: each object's write
        is independent (the reference's controller writebacks are
        per-object patches — one invalid binding must not void a storm
        wave). Rejected objects are skipped (no rv bump, no event) and
        returned as ``[(obj, exception), ...]`` for the caller to surface.
        No ``expected_rv`` support: CAS writers want the single-object
        path. ``status_only`` speaks for the whole batch, as in ``apply``."""
        import time as _time

        if not objs:
            return []
        errors: list = []
        keyed = []
        for obj in objs:
            kind = obj_kind(obj)
            key = obj_key(obj)
            if self._admission is not None:
                try:
                    self._admission(kind, obj)
                except Exception as e:  # noqa: BLE001 — per-object verdict
                    errors.append((obj, e))
                    continue
            keyed.append((kind, key, obj))
        events = []
        with self._lock:
            self.write_count.n += len(keyed)
            for kind, key, obj in keyed:
                bucket = self._buckets.setdefault(kind, {})
                existing = bucket.get(key)
                self._rv += 1
                obj.meta.resource_version = self._rv
                if existing is not None:
                    _move_generation(obj, existing, status_only)
                if not obj.meta.uid:
                    obj.meta.uid = existing.meta.uid if existing else new_uid()
                if existing is None and not obj.meta.creation_timestamp:
                    obj.meta.creation_timestamp = _time.time()
                bucket[key] = obj
                events.append(
                    Event(
                        MODIFIED if existing is not None else ADDED,
                        kind, key, obj,
                    )
                )
        for ev in events:
            self._deliver(ev)
        return errors

    def bump_generation(self, obj: Any) -> None:
        obj.meta.generation += 1

    def delete(self, kind: str, key: str, *, force: bool = False) -> Optional[Any]:
        """Delete an object. With finalizers present (and not force), only
        marks deletion_timestamp and emits MODIFIED — controllers must strip
        finalizers, after which the delete completes (kube semantics).
        ``force`` is the internal finalizer-completion path and skips delete
        admission, like a direct etcd removal."""
        import time

        if not force and self._delete_admission is not None:
            existing = self.get(kind, key)
            if existing is not None:
                self._delete_admission(kind, existing)
        with self._lock:
            bucket = self._buckets.get(kind, {})
            obj = bucket.get(key)
            if obj is None:
                return None
            if obj.meta.finalizers and not force:
                if obj.meta.deletion_timestamp is None:
                    obj.meta.deletion_timestamp = time.time()
                    self._rv += 1
                    self.write_count.n += 1
                    obj.meta.resource_version = self._rv
                    _move_generation(obj, obj, False)
                    event = Event(MODIFIED, kind, key, obj)
                else:
                    return obj
            else:
                del bucket[key]
                self.write_count.n += 1
                event = Event(DELETED, kind, key, obj)
        self._deliver(event)
        return obj

    def finalize(self, obj: Any) -> None:
        """Re-evaluate a deleting object: if finalizers are now empty, remove
        it for real."""
        if obj.meta.deletion_timestamp is not None and not obj.meta.finalizers:
            self.delete(obj_kind(obj), obj_key(obj), force=True)
        else:
            self.apply(obj)

    # -- reads -------------------------------------------------------------

    def get(self, kind: str, key: str) -> Optional[Any]:
        with self._lock:
            return self._buckets.get(kind, {}).get(key)

    def list(self, kind: str, namespace: Optional[str] = None) -> list[Any]:
        with self._lock:
            objs = list(self._buckets.get(kind, {}).values())
        if namespace is not None:
            objs = [o for o in objs if o.meta.namespace == namespace]
        return objs

    # -- durability (checkpoint/resume; SURVEY.md section 5) ---------------

    def checkpoint(self, path: str) -> int:
        """Serialize every object to ``path`` (the etcd-snapshot analogue:
        the store is the single source of truth, controllers and the solver
        are stateless, so a snapshot + replay IS resume). Returns the number
        of objects written."""
        import os
        import pickle

        # Serialize while holding the lock: the bucket copies are shallow
        # and delete()/finalize mutate stored objects' meta IN PLACE under
        # the lock (store.py delete path), including from bus gRPC worker
        # threads — pickling after release could tear the snapshot
        # (tests/test_concurrency_torture.py pins this). The stall is
        # bounded by callers checkpointing only when the rv moved.
        with self._lock:
            payload = {
                kind: dict(bucket) for kind, bucket in self._buckets.items()
            }
            blob = pickle.dumps({"rv": self._rv, "buckets": payload})
        # atomic replace: a crash (or SIGKILL) mid-write must never leave a
        # truncated snapshot that bricks the next restore
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return sum(len(b) for b in payload.values())

    def restore(self, path: str) -> int:
        """Load a checkpoint into this (fresh) store, replaying every object
        through the watch bus as Added so already-registered controllers
        rebuild their working state — the reconcile-from-listing pattern the
        reference relies on after an apiserver restart. Admission is NOT
        re-run: the snapshot was admitted when it was written."""
        import pickle

        with open(path, "rb") as f:
            snap = pickle.load(f)
        events = []
        with self._lock:
            self._rv = max(self._rv, snap["rv"])
            for kind, bucket in snap["buckets"].items():
                dst = self._buckets.setdefault(kind, {})
                for key, obj in bucket.items():
                    dst[key] = obj
                    events.append(Event(ADDED, kind, key, obj))
        for event in events:
            self._deliver(event)
        return len(events)

    def kinds(self) -> Iterable[str]:
        with self._lock:
            return list(self._buckets.keys())

    # -- watch -------------------------------------------------------------

    def watch(self, kind: str, handler: WatchHandler, *, replay: bool = True) -> None:
        """Subscribe to events for one kind. With replay, synthesizes ADDED
        events for existing objects (informer initial-list semantics)."""
        with self._lock:
            self._watchers.setdefault(kind, []).append(handler)
            existing = list(self._buckets.get(kind, {}).items()) if replay else []
        for key, obj in existing:
            handler(Event(ADDED, kind, key, obj))

    def watch_all(self, handler: WatchHandler) -> None:
        with self._lock:
            self._all_watchers.append(handler)

    def unwatch_all(self, handler: WatchHandler) -> None:
        """Unregister a watch_all handler (long-lived stores outlive bus
        servers; a dead server's handler must not stay on the write path)."""
        with self._lock:
            self._all_watchers = [h for h in self._all_watchers if h is not handler]

    def _deliver(self, event: Event) -> None:
        # snapshot the handler lists under the lock, call OUTSIDE it — a
        # handler mutating watchers mid-delivery must not tear the
        # iteration, and delivery under the lock would hold it across
        # arbitrary handler code (the lock is an RLock, but handlers can
        # block on other threads that need the store)
        with self._lock:
            handlers = list(self._watchers.get(event.kind, ()))
            handlers += list(self._all_watchers)
        for handler in handlers:
            handler(event)
