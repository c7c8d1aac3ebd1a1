"""ISSUE 34: the fleet table's upsert phase costs the rows whose content
moved. A batch of the resident batch's length is diffed by object identity,
"same content" is decided against the object a row holds (no fingerprint
tuples), and the rows that change are packed by columns (``_pack_rows``).

(a) parity: random batches through the batched upsert and through a
    row-by-row reference kept HERE (PR 33's ``upsert`` / ``_pack_row`` /
    ``_fingerprint``) leave byte-equal staging, equal ``_dirty``, slot,
    gvk and profile tables and row indices;
(b) the diff: a pass that swaps k of n objects under a moved ``mask_token``
    visits k positions and packs those whose content differs; an
    equal-content swap packs none and pins the NEW object;
(c) PR 33's fault stays repaired: a binding's first object, another, the
    first again repacks both times;
(d) a compaction or growth between two passes falls back to the walk and
    gives the same table;
(e) no container a row: a swap pass whose new objects hold LONGER ``prev``
    dicts than the ones they replace runs no collection;
(f) the counter, the span attributes and the benchmark's reader.
"""

import gc
from dataclasses import replace

import numpy as np
import pytest

from karmada_tpu.api.cluster import Taint
from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.scheduler.fleet import K_EVICT, K_PREV, FleetTable
from karmada_tpu.utils import metrics
from karmada_tpu.utils.tracing import tracer
from test_fleet_failover import (
    NOT_READY,
    C,
    _clusters,
    _copy_out,
    _host_path,
    _place,
    _placements,
    _problem,
    _same,
)

NAMES = [f"m{j:02d}" for j in range(C)]
#: the most previous sites a row of this federation can hold
MOST_PREV = min(K_PREV, C)


class RowByRow(FleetTable):
    """The reference: the upsert phase as PR 33 left it, one row at a time,
    a fingerprint tuple a row, a dozen element assignments a packed row."""

    def __init__(self, engine):
        super().__init__(engine)
        self._fps: list = []

    @staticmethod
    def _fingerprint(p) -> tuple:
        return (
            id(p.placement), p.replicas, p.gvk, p.fresh,
            tuple(p.requests.items()), tuple(p.prev.items()),
            p.evict_clusters,
        )

    def _compact(self) -> bool:
        done = super()._compact()
        if done:
            self._fps = [self._fingerprint(p) for p in self._problems]
        return done

    def upsert(self, problems, compiled, moved=None):
        if self.n_rows + len(problems) > self.cap:
            new_keys = sum(
                1 for p in problems if p.key not in self._key_row
            )
            if self.n_rows + new_keys > self.cap:
                self._compact()
        rows = np.fromiter(
            (self._upsert_one(p, cp) for p, cp in zip(problems, compiled)),
            np.int32, len(problems),
        )
        self._visited_this_pass = len(problems)
        return rows, len(set(rows.tolist())) == len(rows)

    def _upsert_one(self, problem, compiled) -> int:
        row = self._key_row.get(problem.key)
        if row is not None:
            self._st["last_used"][row] = self._pass
            if self._problems[row] is problem:
                return row
            fp = self._fingerprint(problem)
            if fp == self._fps[row]:
                self._problems[row] = problem
                return row
        else:
            if self.n_rows + 1 > self.cap:
                self._grow(self.n_rows + 1)
            row = self.n_rows
            self.n_rows = row + 1
            self._key_row[problem.key] = row
            self._problems.append(problem)
            self._fps.append(None)
            self._terms.append("")
            self._st["last_used"][row] = self._pass
        self._pack_row(row, problem, compiled)
        return row

    def _pack_row(self, row: int, problem, compiled) -> None:
        self._packed_this_pass += 1
        self._problems[row] = problem
        snap = self.engine.snapshot
        st = self._st
        pl = problem.placement
        terms = compiled.terms
        slots = st["term_slots"][row]
        slots[:] = -1
        for t in range(len(terms)):
            slot = self._cp_slot.get(self._slot_key(pl, t))
            if slot is None:
                slot = len(self._cp_pl)
                self._cp_slot[self._slot_key(pl, t)] = slot
                self._cp_pl.append((pl, compiled, t))
                self._slots_minted_this_pass += 1
                self._static_max = max(
                    self._static_max,
                    int(compiled.static_weights.max(initial=0)),
                )
                self._tables_dirty = True
            slots[t] = slot
        st["cp_idx"][row] = slots[0]
        gslot = self._gvk_slot.get(problem.gvk)
        if gslot is None:
            gslot = len(self._gvk_list)
            self._gvk_slot[problem.gvk] = gslot
            self._gvk_list.append(problem.gvk)
            self._tables_dirty = True
        st["gvk_idx"][row] = gslot
        if self._req_slot_snap is not snap:
            self._req_slot = {}
            self._req_slot_snap = snap
        quota = getattr(self.engine, "quota", None)
        qns = (
            quota.cap_index.get(problem.namespace, -1)
            if quota is not None and quota.cap_index
            else -1
        )
        rkey = (tuple(problem.requests.items()), problem.replicas > 0, qns)
        pslot = self._req_slot.get(rkey)
        if pslot is None:
            vec = np.zeros(len(snap.dims), np.int64)
            for d, q in problem.requests.items():
                j = snap.dim_index(d)
                if j is not None:
                    vec[j] = q
            pods = snap.dim_index("pods")
            if pods is not None and problem.replicas > 0:
                vec[pods] = max(vec[pods], 1)
            pkey = vec.tobytes() + qns.to_bytes(4, "little", signed=True)
            pslot = self._prof_slot.get(pkey)
            if pslot is None:
                pslot = len(self._profiles)
                self._prof_slot[pkey] = pslot
                self._profiles.append(vec)
                self._prof_ns.append(qns)
                self._tables_dirty = True
            self._req_slot[rkey] = pslot
        st["prof_idx"][row] = pslot
        st["replicas"][row] = problem.replicas
        st["strategy"][row] = compiled.strategy
        st["fresh"][row] = problem.fresh
        sites = np.zeros(K_PREV, np.int32)
        cnts = np.zeros(K_PREV, np.int32)
        k = 0
        for name, reps_prev in problem.prev.items():
            j = snap.index.get(name)
            if j is not None:
                sites[k] = j
                cnts[k] = reps_prev
                k += 1
        st["prev_sites"][row] = sites
        st["prev_counts"][row] = cnts
        evict = st["evict_sites"][row]
        evict[:] = -1
        k = 0
        for name in problem.evict_clusters:
            j = snap.index.get(name)
            if j is not None:
                evict[k] = j
                k += 1
        st["sel_bits"][row] = 0xFF
        st["sel_on_dev"][row] = False
        self._fps[row] = self._fingerprint(problem)
        self._terms[row] = (
            terms[0][0] if len(terms) == 1 else tuple(n for n, _ in terms)
        )
        self._dirty.add(row)


def _engine(rng, tainted=()):
    snap = ClusterSnapshot(_clusters(rng, tainted, allocated_share=0.3))
    return TensorScheduler(snap, chunk_size=256, mesh=False)


def _upsert_pass(table, engine, problems) -> tuple:
    """The upsert phase of one pass as ``_schedule_pass`` runs it, without
    the device, over the moved positions of the engine's diff against the
    armed record (ResidentBatch.diff), and the record held and armed as
    the engine arms it: (rows, visited, packed, the rows it left dirty)."""
    compiled = [engine._compiled(p.placement) for p in problems]
    rec = table.batch
    diff = None
    if rec is not None and rec.armed and len(rec.problems) == len(problems):
        diff = rec.diff(problems, None, False)
    table._pass += 1
    table._packed_this_pass = table._visited_this_pass = 0
    rows, unique = table.upsert(
        problems, compiled, None if diff is None else diff.moved)
    table._hold(rec, problems, compiled, rows, None, unique, -1)
    table.batch.arm(np.fromiter(map(id, problems), np.int64, len(problems)),
                    None, engine._snapshot_gen, engine.snapshot.mask_token)
    dirty = set(table._dirty)
    table._dirty.clear()  # what _sync_device does once it has uploaded them
    return rows, table._visited_this_pass, table._packed_this_pass, dirty


def _assert_same_table(a: FleetTable, b: FleetTable) -> None:
    assert (a.n_rows, a.cap) == (b.n_rows, b.cap)
    assert a._key_row == b._key_row
    for k in a._st:
        if k == "last_used":
            continue
        x, y = a._st[k][: a.n_rows], b._st[k][: b.n_rows]
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), k
    # a row is live at the pass that walked it or at the resident batch's
    # last pass, whichever the table wrote down
    lu = []
    for t in (a, b):
        eff = t._st["last_used"][: t.n_rows].copy()
        if t.batch is not None and t.batch.rows_np is not None:
            eff[t.batch.rows_np] = t.batch.live
        lu.append(eff.tolist())
    assert lu[0] == lu[1]
    assert all(x is y for x, y in zip(a._problems, b._problems))
    assert a._terms == b._terms
    assert [(id(pl), t) for pl, _, t in a._cp_pl] == [
        (id(pl), t) for pl, _, t in b._cp_pl]
    assert a._cp_slot == b._cp_slot
    assert a._gvk_list == b._gvk_list and a._gvk_slot == b._gvk_slot
    assert [v.tobytes() for v in a._profiles] == [
        v.tobytes() for v in b._profiles]
    assert a._prof_ns == b._prof_ns
    assert a._static_max == b._static_max
    assert a._tables_dirty == b._tables_dirty
    assert a._slots_minted_this_pass == b._slots_minted_this_pass


def _twin(p, **changes):
    """Another object for the same binding: equal content, or changed."""
    return replace(p, **{
        "requests": dict(p.requests), "prev": dict(p.prev), **changes})


def _changed(rng, p):
    """Another object for the same binding whose content differs: one more
    replica (so it always does), and one field more."""
    kind = int(rng.integers(0, 6))
    more = {"replicas": p.replicas + 1}
    if kind == 1:
        k = int(rng.integers(0, MOST_PREV + 1))
        more["prev"] = {NAMES[j]: int(rng.integers(1, 9))
                        for j in rng.choice(C, k, replace=False)}
    elif kind == 2:
        k = int(rng.integers(0, K_EVICT + 1))
        more["evict_clusters"] = tuple(
            NAMES[j] for j in rng.choice(C, k, replace=False))
    elif kind == 3:
        more["fresh"] = not p.fresh
    elif kind == 4:
        more["requests"] = {"cpu": 1000 * int(rng.integers(3, 6))}
    elif kind == 5:
        more["gvk"] = "batch/v1/Job"
    return _twin(p, **more)


def _batches(rng, placements, n: int) -> list:
    """A first pass of n new keys, then what passes bring: swapped objects
    (equal and changed content), the same list again, new keys, another
    length, a key twice in one batch, another placement for a binding."""
    base = [_problem(rng, i, placements[i % len(placements)])
            for i in range(n)]
    # a row of each edge: as many previous sites as there are, no replicas
    base[0] = _twin(base[0], prev={
        NAMES[j]: 1 + j for j in range(MOST_PREV)})
    base[1] = _twin(base[1], replicas=0)
    out = [base]
    cur = list(base)
    for _ in range(3):  # swaps: the diff's case
        nxt = list(cur)
        for i in rng.choice(n, n // 5, replace=False):
            nxt[i] = (_twin(cur[i]) if rng.random() < 0.4
                      else _changed(rng, cur[i]))
        out.append(nxt)
        cur = nxt
    out.append(cur)  # the same list object again: the identity path's case
    moved = list(cur)  # a binding under another placement
    for i in rng.choice(n, 7, replace=False):
        moved[i] = _twin(cur[i], placement=placements[
            (i + 1) % len(placements)])
    out.append(moved)
    grown = list(moved) + [  # another length, new keys
        _problem(rng, n + i, placements[i % len(placements)])
        for i in range(n // 4)]
    out.append(grown)
    twice = list(grown)  # a key twice: equal, then changed content
    twice[3] = _twin(grown[5])
    twice[9] = _changed(rng, grown[11])
    out.append(twice)
    again = list(twice)  # the same length over a batch with a key twice
    for i in rng.choice(len(again), 20, replace=False):
        again[i] = _changed(rng, again[i])
    out.append(again)
    out.append(list(again[: n // 2]))  # a shorter batch
    swapped = list(again[: n // 2])
    swapped[0], swapped[1] = swapped[1], swapped[0]  # keys at other rows
    out.append(swapped)
    return out


# -- (a) ---------------------------------------------------------------------


@pytest.mark.parametrize("seed", [5, 2147483777, 3200100999])
def test_the_batched_upsert_leaves_the_row_by_row_table(seed):
    rng = np.random.default_rng(seed)
    engine = _engine(rng)
    placements = _placements(rng)
    got, want = FleetTable(engine), RowByRow(engine)
    visited = []
    for b, problems in enumerate(_batches(rng, placements, 300)):
        a = _upsert_pass(got, engine, problems)
        r = _upsert_pass(want, engine, problems)
        assert a[0].tolist() == r[0].tolist(), b
        assert a[2] == r[2], (b, "rows packed")
        assert a[3] == r[3], (b, "dirty rows")
        _assert_same_table(got, want)
        visited.append((a[1], r[1], len(problems)))
    # the diff engaged where it can, and only there: the swaps, the same
    # list, another placement; not the first pass, another length, the
    # batch that follows one with a key twice, keys at other rows
    assert [v for v, _, _ in visited] == [
        300, 60, 60, 60, 0, 7, 375, 375, 375, 150, 150]
    assert all(w == n for _, w, n in visited)


def test_a_key_twice_in_one_batch_keeps_its_last_binding():
    rng = np.random.default_rng(11)
    engine = _engine(rng)
    placements = _placements(rng)
    p = _problem(rng, 0, placements[0])
    q = _changed(rng, p)
    got, want = FleetTable(engine), RowByRow(engine)
    batch = [p, q, _twin(q)]
    for table in (got, want):
        rows, _, packed, _ = _upsert_pass(table, engine, batch)
        assert rows.tolist() == [0, 0, 0] and packed == 2
    _assert_same_table(got, want)
    assert got._problems[0].replicas == q.replicas


def test_a_row_with_no_room_for_its_tasks_is_refused():
    """The flat cells of a column pack must not run into the next row's
    (the engine's ``row_rides`` keeps such a binding off the fleet)."""
    rng = np.random.default_rng(13)
    engine = _engine(rng)
    placements = _placements(rng)
    problems = [_problem(rng, i, placements[0]) for i in range(4)]
    problems[1] = _twin(problems[1], evict_clusters=tuple(
        NAMES[: K_EVICT + 1]))
    with pytest.raises(IndexError):
        _upsert_pass(FleetTable(engine), engine, problems)


# -- (b), (c) ----------------------------------------------------------------


def _region_loss(rng):
    """A federation before and after the loss of region r1, and a batch in
    both states: the bindings the loss evicts come as NEW objects."""
    clusters = _clusters(rng, allocated_share=0.3)
    healthy = ClusterSnapshot(clusters)
    lost_at = [j for j in range(C) if _place(j)[0] == "r1"]
    lost = {NAMES[j] for j in lost_at}
    for j in lost_at:
        clusters[j].spec.taints = [Taint(key=NOT_READY, effect="NoExecute")]
    tainted = ClusterSnapshot(clusters)
    assert healthy.mask_token != tainted.mask_token
    placements = _placements(rng, terms=(1, 2, 3))
    base = [_problem(rng, i, placements[i % len(placements)])
            for i in range(600)]
    for p in base:
        p.evict_clusters = ()
    after, content_moved = [], 0
    for p in base:
        hit = [n for n in p.prev if n in lost]
        if hit and not p.placement.cluster_tolerations:
            after.append(_twin(
                p, prev={n: v for n, v in p.prev.items() if n not in lost},
                evict_clusters=tuple(hit[:K_EVICT])))
            content_moved += 1
        elif hit:
            after.append(_twin(p))  # presented anew, nothing moved
        else:
            after.append(p)
    return healthy, tainted, base, after, content_moved


def _solve_spans() -> list:
    return [s for s in tracer.dump() if s["name"] == "scheduler.solve"]


def test_a_swapped_batch_is_diffed_not_walked():
    rng = np.random.default_rng(23)
    healthy, tainted, base, after, content_moved = _region_loss(rng)
    swapped = sum(1 for a, b in zip(after, base) if a is not b)
    assert 100 < content_moved < swapped < 400
    engine = TensorScheduler(healthy, chunk_size=256, mesh=False)
    tracer.clear()
    engine.schedule(base)
    (first,) = _solve_spans()
    assert first["attrs"]["rows_visited"] == 600
    assert first["attrs"]["rows_packed"] == 600
    table = engine._fleet
    tally = {o: metrics.fleet_upsert_rows.value(outcome=o)
             for o in ("same", "equal", "packed")}
    for turn in range(2):
        for snap, problems, packed in (
            # (b) the loss: the full prologue under a moved mask_token,
            # other lists; (c) the return: the first objects again
            (tainted, after, content_moved), (healthy, base, content_moved),
        ):
            assert engine.update_snapshot(snap)
            tracer.clear()
            got = _copy_out(engine.schedule(problems))
            assert "eligible" in engine.last_breakdown  # the full prologue
            (solve,) = _solve_spans()
            assert solve["attrs"]["rows_visited"] == swapped
            assert solve["attrs"]["rows_packed"] == packed
            assert solve["attrs"]["rows_replayed"] == 600 - packed
            assert engine.last_breakdown["rows_visited"] == swapped
            (upsert,) = [s for s in tracer.dump()
                         if s["name"] == "kernel.host"
                         and s["attrs"].get("phase") == "upsert"]
            assert upsert["attrs"]["rows_visited"] == swapped
            assert upsert["attrs"]["rows_packed"] == packed
            assert upsert["parent_id"] == solve["span_id"]
            # every row holds the object the pass brought
            rows = table.batch.rows_np
            assert all(table._problems[r] is p
                       for r, p in zip(rows, problems))
            want = _host_path(snap, problems)
            for i, (a, b) in enumerate(zip(got, want)):
                _same(a, b, i)
            # the same lists again: the identity path looks at no position
            tracer.clear()
            engine.schedule(problems)
            (solve,) = _solve_spans()
            assert solve["attrs"]["rows_visited"] == 0
            assert solve["attrs"]["rows_packed"] == 0
    moved = {o: metrics.fleet_upsert_rows.value(outcome=o) - tally[o]
             for o in tally}
    assert moved == {
        "packed": 4 * content_moved,
        "equal": 4 * (swapped - content_moved),
        "same": 4 * (600 - swapped) + 4 * 600,
    }


def test_an_equal_content_swap_packs_nothing_and_pins_the_newcomer():
    rng = np.random.default_rng(29)
    engine = _engine(rng)
    placements = _placements(rng)
    first = [_problem(rng, i, placements[i % len(placements)])
             for i in range(400)]
    engine.schedule(first)
    table = engine._fleet
    second = list(first)
    picks = rng.choice(400, 90, replace=False).tolist()
    for i in picks:
        second[i] = _twin(first[i])
    st = {k: v[: table.n_rows].copy() for k, v in table._st.items()}
    rows, visited, packed, dirty = _upsert_pass(table, engine, second)
    assert (visited, packed, dirty) == (90, 0, set())
    assert all(table._problems[rows[i]] is second[i] for i in picks)
    assert all(np.array_equal(st[k], table._st[k][: table.n_rows])
               for k in st if k != "last_used")
    # the next diff is against the newcomers: changing one of them is one
    # visit, not ninety
    third = list(second)
    third[picks[0]] = _twin(second[picks[0]], replicas=77)
    rows, visited, packed, dirty = _upsert_pass(table, engine, third)
    assert (visited, packed, dirty) == (1, 1, {int(rows[picks[0]])})
    assert table._st["replicas"][rows[picks[0]]] == 77


# -- (d) ---------------------------------------------------------------------


@pytest.mark.parametrize("event", ["growth", "compaction"])
def test_a_remapped_table_falls_back_to_the_walk(event):
    rng = np.random.default_rng(37)
    engine = _engine(rng)
    placements = _placements(rng)
    a = [_problem(rng, i, placements[i % len(placements)])
         for i in range(400)]
    b = [_problem(rng, 1000 + i, placements[i % len(placements)])
         for i in range(100)]
    b2 = list(b)
    for i in range(0, 100, 3):
        b2[i] = _changed(rng, b[i])
    got, want = FleetTable(engine), RowByRow(engine)
    for table in (got, want):
        _upsert_pass(table, engine, a)
        for _ in range(FleetTable.COMPACT_IDLE_PASSES + 2):
            _upsert_pass(table, engine, b)
        assert table.n_rows == 500 and table.cap == 512
        if event == "growth":
            table._grow(table.cap * 2)
        else:
            assert table._compact() and table.n_rows == 100
        assert table.batch.rows_np is None
        # the resident batch's length, but no rows of it: the walk
        _, visited, packed, _ = _upsert_pass(table, engine, b2)
        assert (visited, packed) == (100, 34)
    _assert_same_table(got, want)
    # and from there the diff again
    b3 = list(b2)
    b3[7] = _changed(rng, b2[7])
    assert _upsert_pass(got, engine, b3)[1:3] == (1, 1)
    _upsert_pass(want, engine, b3)
    _assert_same_table(got, want)


def test_a_batch_that_outgrows_the_table_compacts_then_grows():
    rng = np.random.default_rng(41)
    engine = _engine(rng)
    placements = _placements(rng)
    old = [_problem(rng, i, placements[i % len(placements)])
           for i in range(200)]
    keep = [_problem(rng, 1000 + i, placements[i % len(placements)])
            for i in range(40)]
    big = keep + [_problem(rng, 2000 + i, placements[i % len(placements)])
                  for i in range(700)]
    got, want = FleetTable(engine), RowByRow(engine)
    for table in (got, want):
        _upsert_pass(table, engine, old)
        for _ in range(FleetTable.COMPACT_IDLE_PASSES + 2):
            _upsert_pass(table, engine, keep)
        assert (table.n_rows, table.cap) == (240, 256)
        _upsert_pass(table, engine, big)
        assert (table.n_rows, table.cap) == (740, 1024)
    _assert_same_table(got, want)


# -- (e) ---------------------------------------------------------------------


def _collections() -> int:
    return sum(g["collections"] for g in gc.get_stats())


@pytest.mark.parametrize("table_of", [FleetTable, RowByRow])
def test_a_swap_pass_allocates_no_container_a_row(table_of):
    """The region-loss ring's ``r`` wave: the returning objects hold longer
    ``prev`` dicts than the ones they replace. The reference's fingerprint
    tuples then outnumber the freed ones and trip the collector; the table
    itself keeps nothing a row."""
    rng = np.random.default_rng(43)
    engine = _engine(rng)
    placements = _placements(rng)
    n = 2000
    short = [
        BindingProblem(
            key=f"b{i}", placement=placements[i % len(placements)],
            replicas=3, requests={"cpu": 1000}, gvk="apps/v1/Deployment",
            prev={NAMES[i % C]: 3})
        for i in range(n)
    ]
    longer = [
        _twin(p, prev={NAMES[(i + j) % C]: 1 + j for j in range(MOST_PREV)})
        for i, p in enumerate(short)
    ]
    table = table_of(engine)
    _upsert_pass(table, engine, short)
    _upsert_pass(table, engine, [_twin(p) for p in longer])
    _upsert_pass(table, engine, [_twin(p) for p in short])
    assert gc.isenabled() and gc.get_threshold()[0] <= 700
    gc.collect()
    before = _collections()
    _, visited, packed, _ = _upsert_pass(table, engine, longer)
    ran = _collections() - before
    assert (visited, packed) == (n, n)
    if table_of is FleetTable:
        assert ran == 0
    else:
        assert ran > 0  # what the fingerprints cost, or (e) shows nothing


# -- (f) ---------------------------------------------------------------------


def test_the_reader_takes_the_swap_passes_upsert_phase():
    from benchmark.metrics import swap_upsert_s

    def span(name, span_id, parent, start, dur, **attrs):
        return {"name": name, "span_id": span_id, "parent_id": parent,
                "start": start, "duration_s": dur, "attrs": attrs}

    spans = [
        # an h pass: the identity path, nothing packed
        span("scheduler.solve", 1, None, 10.0, 0.07, rows_packed=0),
        span("kernel.host", 2, 1, 10.0, 0.0001, phase="upsert"),
        # an L pass
        span("scheduler.solve", 3, None, 11.0, 0.40, rows_packed=17213),
        span("kernel.host", 4, 3, 11.0, 0.25, phase="upsert"),
        span("kernel.host", 5, 3, 11.25, 0.02, phase="sync"),
        # an r pass on a program that names no parent: by the interval
        span("scheduler.solve", 6, None, 12.0, 0.50, rows_packed=17213),
        span("kernel.host", 7, None, 12.0, 0.35, phase="upsert"),
        # a pass that packed, outside the waves the readers take
        span("scheduler.solve", 8, None, 2.0, 0.40, rows_packed=100000),
        span("kernel.host", 9, 8, 2.0, 0.39, phase="upsert"),
    ]
    ctx = {"spans": spans, "waves": [(9.9, 10.1), (10.9, 11.5), (11.9, 12.6)]}
    assert swap_upsert_s.read(ctx) == pytest.approx(0.30)
    ctx["spans"] = spans[:2]
    assert swap_upsert_s.read(ctx) is None
    # a program whose solve spans carry no such attribute: nothing to read
    ctx["spans"] = [span("scheduler.solve", 1, None, 10.0, 0.07)]
    assert swap_upsert_s.read(ctx) is None
