"""ISSUE 38: a resident batch keeps what a pass derives from its rows. The
fleet table's record of its resident batch (``ResidentBatch.derived``, read
through ``_batch_derived``) holds what a pass derives from its row state: the
affinity names by position, the largest ``replicas`` and previous count
(``kernel_variant``'s inputs), ``has_agg``, ``is_dup``, ``need_bits``,
``is_all``. It is kept while the same row vector comes again and no row of
the batch was packed, and built anew otherwise.

(a) two identity passes: ``derived`` built then kept, one ``terms`` list,
    every row as a fresh table answers it; a result list of the first pass
    names its own terms after the second;
(b) a swap under a moved ``mask_token``: built once, then kept; multi-term
    rows' tuples resolve to the chosen term;
(c) rows repacked IN PLACE while the batch's row vector keeps its object
    (the delta path, and the table's upsert of a sub-batch): the record
    equals a fresh table's: an identity-only key fails the second route;
(d) growth and compaction build, a ``mask_token`` move that rebuilds the
    slot tables without packing keeps;
(e) kept + built = passes on the counter.
"""

import numpy as np
import pytest

from karmada_tpu.scheduler import ClusterSnapshot
from karmada_tpu.scheduler.fleet import _FleetResultList
from karmada_tpu.utils import metrics
from karmada_tpu.utils.tracing import tracer
from test_engine_swap import _after, _batch, _engine, _federation, _spans
from test_fleet_failover import (
    _clusters,
    _copy_out,
    _placement,
    _placements,
    _same,
)
from test_fleet_upsert import _twin

FIELDS = ("max_n", "max_prev", "has_agg", "need_bits", "is_all")


def _derived_of() -> list:
    return [s["attrs"].get("derived") for s in _spans("scheduler.solve")]


def _prep_derived() -> list:
    return [s["attrs"]["derived"] for s in _spans("kernel.host")
            if s["attrs"].get("phase") == "prep"]


def _tally() -> tuple:
    c = metrics.fleet_batch_derived
    return c.value(outcome="kept"), c.value(outcome="built")


def _record(engine):
    return engine._fleet._batch_derived()


def _same_record(got, want) -> None:
    for f in FIELDS:
        assert getattr(got, f) == getattr(want, f), f
    assert got.terms == want.terms
    assert np.array_equal(got.is_dup, want.is_dup)


def _same_as_fresh(snap, problems, got):
    """Every row as a fresh engine answers it; returns that engine."""
    fresh = _engine(snap)
    want = _copy_out(fresh.schedule(problems))
    assert len(got) == len(want) == len(problems)
    for i, (a, b) in enumerate(zip(got, want)):
        _same(a, b, i)
    return fresh


# -- (a) ---------------------------------------------------------------------


def test_identity_passes_keep_what_the_first_derived():
    rng = np.random.default_rng(38)
    healthy, _, _ = _federation(rng)
    base = _batch(rng, _placements(rng, terms=(1, 2, 3)))
    engine = _engine(healthy)
    tracer.clear()
    res1 = engine.schedule(base)
    assert isinstance(res1, _FleetResultList)
    # one position of the first pass is read before the second, one after
    multi = [i for i, p in enumerate(base)
             if len(p.placement.cluster_affinities) > 1]
    early, late = multi[0], multi[-1]
    name_early = res1[early].affinity_name
    first = _copy_out(engine.schedule(base))  # identity
    res2 = engine.schedule(base)  # identity again
    assert _derived_of() == ["built", "kept", "kept"]
    assert _prep_derived() == ["built", "kept", "kept"]
    assert res2._terms is res1._terms
    assert res2._is_dup is res1._is_dup
    got = _copy_out(res2)
    fresh = _same_as_fresh(healthy, base, got)
    assert np.array_equal(res2._n_placed, fresh.schedule(base)._n_placed)
    for a, b in zip(got, first):
        assert (a.clusters, a.error, a.affinity_name) == (
            b.clusters, b.error, b.affinity_name)
    # the first pass's list answers for its own pass still
    assert res1[early].affinity_name == name_early == got[early].affinity_name
    assert res1[late].affinity_name == got[late].affinity_name
    _same_record(_record(engine), _record(fresh))


# -- (b) ---------------------------------------------------------------------


def test_a_swap_builds_once_then_keeps():
    rng = np.random.default_rng(3802)
    healthy, tainted, lost = _federation(rng)
    base = _batch(rng, _placements(rng, terms=(1, 2, 3)))
    after = _after(base, lost)
    engine = _engine(healthy)
    old = engine.schedule(base)
    old_names = [old[i].affinity_name for i in range(0, len(base), 5)]
    assert engine.update_snapshot(tainted)
    tracer.clear()
    engine.schedule(after)
    got = _copy_out(engine.schedule(after))
    got2 = _copy_out(engine.schedule(after))
    solves = _spans("scheduler.solve")
    assert [s["attrs"]["derived"] for s in solves] == ["built", "kept", "kept"]
    assert solves[0]["attrs"]["rows_packed"] > 0
    assert solves[1]["attrs"]["rows_packed"] == 0
    fresh = _same_as_fresh(tainted, after, got)
    _same_as_fresh(tainted, after, got2)
    # multi-term rows hold their terms' names and read the chosen one
    terms = engine._fleet.batch.derived.terms
    multi = [i for i, t in enumerate(terms) if t.__class__ is tuple]
    assert len(multi) > 100
    assert {terms[i].index(got[i].affinity_name) for i in multi} >= {0, 1}
    _same_record(_record(engine), _record(fresh))
    # the list of the pass before the swap kept its own names' list
    assert old._terms is not terms
    assert [old[i].affinity_name
            for i in range(0, len(base), 5)] == old_names


# -- (c) ---------------------------------------------------------------------


def _quiet_batch(rng, n=400):
    """(snapshot, batch, placements to turn rows to): a batch of Divided
    single-term rows of 1-9 replicas and previous counts under 5, so each
    derived value has room to move."""
    snap = ClusterSnapshot(_clusters(rng, allocated_share=0.3))
    dynamic = [_placement(rng, "dynamic", 1, tol) for tol in (False, True)]
    base = _batch(rng, dynamic, n)
    names = snap.names
    for i, p in enumerate(base):
        p.replicas = 1 + i % 9
        p.prev = {names[(i + k) % len(names)]: 1 + k for k in range(i % 4)}
    turned = {
        "aggregated": _placement(rng, "aggregated", 1, True),
        "duplicated": _placement(rng, "duplicated", 1, True),
        "renamed": _placement(rng, "dynamic", 3, True),
    }
    return snap, base, turned


def _repacked(snap, base, turned) -> tuple:
    """The batch with five positions holding other objects: replicas past
    the old maximum, a previous count past the old maximum, a row turned
    Aggregated, one turned Duplicated, one under other affinity names."""
    out = list(base)
    out[3] = _twin(base[3], replicas=100)
    out[17] = _twin(base[17], prev={snap.names[0]: 90})
    out[40] = _twin(base[40], placement=turned["aggregated"])
    out[77] = _twin(base[77], placement=turned["duplicated"])
    out[120] = _twin(base[120], placement=turned["renamed"])
    return out, [3, 17, 40, 77, 120]


@pytest.mark.parametrize("route", ("engine-delta", "table-upsert"))
def test_rows_repacked_in_place_rebuild_the_record(route):
    rng = np.random.default_rng(3803)
    snap, base, turned = _quiet_batch(rng)
    engine = _engine(snap)
    engine.schedule(base)
    res_before = engine.schedule(base)
    table = engine._fleet
    rows_full = table.batch.rows_np
    before = table.batch.derived
    assert before.rows_np is rows_full
    assert (before.max_n, before.max_prev) == (9, 3)
    assert not (before.has_agg or before.need_bits)
    after, moved = _repacked(snap, base, turned)
    if route == "engine-delta":
        tracer.clear()
        got = _copy_out(engine.schedule(after))
        (root,) = _spans("scheduler.schedule")
        assert root["attrs"]["path"] == "delta"
        assert _derived_of() == ["built"]
        assert table.last_breakdown["dirty_rows"] == len(moved)
        fresh = _same_as_fresh(snap, after, got)
    else:
        # what the delta path's sub-pass does to the table: the moved
        # positions' rows packed in place; the record goes with them, and
        # one held again over the batch's same row vector derives anew
        sub = [after[i] for i in moved]
        table.upsert(sub, [engine._compiled(p.placement) for p in sub])
        assert table.batch is None
        table._hold(
            None, after, [engine._compiled(p.placement) for p in after],
            rows_full, None, True, -1,
        )
        fresh = _engine(snap)
        fresh.schedule(after)
    # the batch's row vector kept its object, the record did not stand
    assert table.batch.rows_np is rows_full
    now = table._batch_derived()
    assert now is not before
    _same_record(now, _record(fresh))
    assert (now.max_n, now.max_prev) == (100, 90)
    assert now.has_agg and now.need_bits and now.is_dup[77]
    assert now.terms[120] == ("t0", "t1", "t2")
    # the earlier pass's list holds the names of its pass
    assert before.terms[120] == res_before._terms[120] == "t0"
    assert res_before._terms is before.terms
    if route == "engine-delta":
        tracer.clear()
        again = _copy_out(engine.schedule(after))
        assert _derived_of() == ["kept"]
        for i, (a, b) in enumerate(zip(again, got)):
            _same(a, b, i)


# -- (d) ---------------------------------------------------------------------


def _armed(rng):
    healthy, tainted, _ = _federation(rng)
    base = _batch(rng, _placements(rng, terms=(1, 2)), n=700)
    engine = _engine(healthy)
    engine.schedule(base)
    tracer.clear()
    engine.schedule(base)
    assert _derived_of() == ["kept"]
    return engine, healthy, tainted, base


def _grown(engine, healthy, tainted, base):
    engine._fleet._grow(engine._fleet.cap * 2)
    return healthy, base, "built"


def _compacted(engine, healthy, tainted, base):
    table = engine._fleet
    few = base[:300]  # a fleet batch still, under half the rows
    for _ in range(table.COMPACT_IDLE_PASSES + 1):
        engine.schedule(few)
    assert table._compact()
    assert table.n_rows == len(few)
    return healthy, few, "built"


def _token_moved(engine, healthy, tainted, base):
    assert engine.update_snapshot(tainted)
    return tainted, base, "kept"


@pytest.mark.parametrize("event", (_grown, _compacted, _token_moved))
def test_what_drops_the_record_and_what_does_not(event):
    rng = np.random.default_rng(3804)
    engine, *rest = _armed(rng)
    table = engine._fleet
    rebuilds = metrics.fleet_table_rebuilds.value()
    snap, batch, outcome = event(engine, *rest)
    assert (table.batch.derived is None) == (outcome == "built")
    tracer.clear()
    got = _copy_out(engine.schedule(batch))
    (solve,) = _spans("scheduler.solve")
    assert solve["attrs"]["derived"] == outcome
    # a growth or a compaction has the batch walked; no row is packed
    assert solve["attrs"]["rows_packed"] == 0
    assert solve["attrs"]["rows_visited"] == (
        0 if event is _token_moved else len(batch))
    if event is _token_moved:
        # the slot tables were rebuilt for the new token, no row packed
        (root,) = _spans("scheduler.schedule")
        assert root["attrs"]["path"] == "full"
        assert table._snapshot_gen == engine._snapshot_gen
    assert metrics.fleet_table_rebuilds.value() == rebuilds
    fresh = _same_as_fresh(snap, batch, got)
    _same_record(_record(engine), _record(fresh))


# -- (e) ---------------------------------------------------------------------


def test_the_counter_counts_every_pass_once():
    rng = np.random.default_rng(3805)
    snap, base, turned = _quiet_batch(rng, n=700)
    after, _ = _repacked(snap, base, turned)
    engine = _engine(snap)
    kept0, built0 = _tally()
    tracer.clear()
    for batch in (base, base, after, after, base, base[:300], base[:300]):
        engine.schedule(batch)
    kept, built = _tally()
    outcomes = _derived_of()
    assert len(outcomes) == 7
    assert outcomes == ["built", "kept", "built", "kept", "built", "built",
                        "kept"]
    assert (kept - kept0, built - built0) == (
        outcomes.count("kept"), outcomes.count("built"))
