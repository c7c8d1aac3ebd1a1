"""Cross-process wave tracing (ISSUE 10): context propagation over the
estimator/solver/bus channels, the stitcher, /debug/traces query
handling, and the slow-wave flight recorder.

Cross-process shape in one test process: the SERVER side of each gRPC
seam binds the tracer object at construction, so constructing a server
while a second ``WaveTracer`` (proc="estimator"/"solver"/"bus") is
installed as the module global gives that server its own ring — the
client side resolves the real global (proc="plane") at call time.  The
two rings then stitch exactly like two processes' /debug/traces dumps.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import karmada_tpu.utils.tracing as tracing
from karmada_tpu.utils.tracing import (
    ContextPropagatingExecutor,
    TraceContext,
    WaveTracer,
    decode_trace_metadata,
    stitch_dumps,
    trace_debug_doc,
    trace_metadata,
    tracer,
)

DIMS = ["cpu", "memory", "pods"]


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.clear()
    tracer.set_process("plane")
    tracing.clear_peers()
    yield
    tracer.clear()
    tracing.clear_peers()


@pytest.fixture()
def server_tracer(monkeypatch):
    """A second ring standing in for a remote process: installed as the
    module global only while the caller constructs its gRPC server (the
    handlers bind the tracer object at construction)."""
    tr = WaveTracer()

    def build(proc_name, ctor):
        tr.set_process(proc_name)
        monkeypatch.setattr(tracing, "tracer", tr)
        try:
            return ctor()
        finally:
            monkeypatch.setattr(tracing, "tracer", tracer)

    build.ring = tr
    return build


# --------------------------------------------------------------------------
# context + metadata
# --------------------------------------------------------------------------


class TestTraceMetadata:
    def test_roundtrip(self):
        ctx = TraceContext(wave=7, trace_id="abc123", span_id=42, proc="plane")
        assert decode_trace_metadata(trace_metadata(ctx)) == ctx

    def test_no_context_is_empty(self):
        assert trace_metadata(None) == ()
        assert trace_metadata(
            TraceContext(wave=0, trace_id="", span_id=None, proc="plane")
        ) == ()

    def test_span_id_none_roundtrip(self):
        ctx = TraceContext(wave=1, trace_id="t", span_id=None, proc="agent")
        assert decode_trace_metadata(trace_metadata(ctx)) == ctx

    @pytest.mark.parametrize(
        "pairs",
        [
            (),
            None,
            (("karmada-tpu-wave", "3"),),  # no trace id
            (("karmada-tpu-trace", "t"), ("karmada-tpu-wave", "NaNope")),
            (("karmada-tpu-trace", "t"), ("karmada-tpu-span", "xyz")),
            ("not-a-pair",),
        ],
    )
    def test_malformed_metadata_decodes_none(self, pairs):
        """An untraced or garbled caller must never fail the RPC."""
        assert decode_trace_metadata(pairs) is None

    def test_foreign_metadata_ignored(self):
        pairs = (
            ("user-agent", "grpc-python"),
            ("karmada-tpu-trace", "t1"),
            ("karmada-tpu-wave", "4"),
            ("karmada-tpu-span", "9"),
            ("karmada-tpu-proc", "plane"),
        )
        ctx = decode_trace_metadata(pairs)
        assert ctx == TraceContext(wave=4, trace_id="t1", span_id=9,
                                   proc="plane")


# --------------------------------------------------------------------------
# tracer satellites: lock-stamped wave ids, end_wave return, evictions
# --------------------------------------------------------------------------


class TestTracerSatellites:
    def test_end_wave_returns_closed_id(self):
        tr = WaveTracer()
        w = tr.begin_wave("test")
        assert tr.end_wave() == w
        # idempotent close still names the last wave
        assert tr.end_wave() == w

    def test_span_keeps_wave_stamped_at_open(self):
        """A span opened before end_wave() but closed after a NEW wave
        began stays attributed to the wave it opened under."""
        tr = WaveTracer()
        w1 = tr.begin_wave("one")
        opened = threading.Event()
        release = threading.Event()

        def straggler():
            with tr.span("settle"):
                opened.set()
                release.wait(5)

        t = threading.Thread(target=straggler)
        t.start()
        assert opened.wait(5)
        assert tr.end_wave() == w1
        w2 = tr.begin_wave("two")
        release.set()
        t.join(5)
        tr.end_wave()
        spans = tr.dump(w1)
        assert [s["name"] for s in spans] == ["settle"]
        assert not tr.dump(w2)

    def test_wave_trace_ids_unique(self):
        tr = WaveTracer()
        w1 = tr.begin_wave()
        t1 = tr.wave_trace_id(w1)
        tr.end_wave()
        w2 = tr.begin_wave()
        t2 = tr.wave_trace_id(w2)
        assert t1 and t2 and t1 != t2

    def test_ring_eviction_counted(self):
        tr = WaveTracer(capacity=16)
        w = tr.begin_wave("storm")
        for i in range(40):
            tr.record("scheduler.pack", 0.001, i=i)
        tr.end_wave()
        assert len(tr.dump()) == 16
        assert tr.dropped_total == 24
        summary = tr.wave_summary(w)
        assert summary["dropped"] == 24
        # the registry counter moved in lockstep
        from karmada_tpu.utils.metrics import trace_spans_dropped

        assert trace_spans_dropped.value() >= 24

    def test_capacity_env_tunable(self, monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_CAPACITY", "32")
        assert WaveTracer().capacity == 32
        monkeypatch.setenv("KARMADA_TPU_TRACE_CAPACITY", "bogus")
        assert WaveTracer().capacity == 32768
        monkeypatch.delenv("KARMADA_TPU_TRACE_CAPACITY")
        assert WaveTracer(capacity=7).capacity == 7

    def test_debug_doc_surfaces_dropped(self):
        tr = WaveTracer(capacity=8)
        tr.begin_wave()
        for _ in range(20):
            tr.record("scheduler.pack", 0.001)
        tr.end_wave()
        doc = trace_debug_doc(tracer_obj=tr)
        assert doc["dropped"] == 12

    def test_executor_context_propagation(self):
        from concurrent.futures import ThreadPoolExecutor

        tr = WaveTracer()
        pool = ContextPropagatingExecutor(ThreadPoolExecutor(2), tr)
        w = tr.begin_wave("fanout")
        with tr.span("estimator.refresh") as parent:
            futs = [
                pool.submit(lambda: tr.record("estimator.rpc", 0.001))
                for _ in range(4)
            ]
            spans = [f.result(5) for f in futs]
        tr.end_wave()
        for sp in spans:
            assert sp.wave == w
            assert sp.parent_id == parent.span_id
        pool.shutdown()


# --------------------------------------------------------------------------
# /debug/traces query handling
# --------------------------------------------------------------------------


class TestDebugTracesQueries:
    @pytest.fixture()
    def server(self):
        from karmada_tpu.utils.metrics import MetricsServer

        srv = MetricsServer()
        port = srv.start()
        yield port
        srv.stop()

    def _get(self, port, path):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as resp:
            return json.loads(resp.read().decode())

    def test_wave_filter(self, server):
        w1 = tracer.begin_wave()
        with tracer.span("settle"):
            pass
        tracer.end_wave()
        w2 = tracer.begin_wave()
        with tracer.span("settle"):
            with tracer.span("scheduler.pass"):
                pass
        tracer.end_wave()
        doc = self._get(server, f"/debug/traces?wave={w2}")
        assert {s["wave"] for s in doc["spans"]} == {w2}
        assert [w["wave"] for w in doc["waves"]] == [w2]
        assert len(doc["spans"]) == 2
        doc1 = self._get(server, f"/debug/traces?wave={w1}")
        assert len(doc1["spans"]) == 1

    def test_summary_drops_spans(self, server):
        tracer.begin_wave()
        with tracer.span("settle"):
            pass
        tracer.end_wave()
        doc = self._get(server, "/debug/traces?summary=1")
        assert "spans" not in doc
        assert doc["waves"]
        full = self._get(server, "/debug/traces?summary=0")
        assert "spans" in full

    def test_malformed_wave_is_400(self, server):
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            self._get(server, "/debug/traces?wave=banana")
        assert exc_info.value.code == 400
        body = json.loads(exc_info.value.read().decode())
        assert "banana" in body["error"]

    def test_doc_carries_proc_and_peers(self, server):
        tracing.register_peer("solver", "127.0.0.1:1")
        doc = self._get(server, "/debug/traces")
        assert doc["proc"] == "plane"
        assert doc["peers"] == {"solver": "127.0.0.1:1"}
        assert "dropped" in doc and "mesh" in doc


# --------------------------------------------------------------------------
# estimator channel propagation (real gRPC, two rings)
# --------------------------------------------------------------------------


def _estimator_service(name="c1"):
    from karmada_tpu.estimator.accurate import (
        AccurateEstimator,
        NodeCache,
        NodeState,
    )
    from karmada_tpu.estimator.service import EstimatorService

    cache = NodeCache(
        DIMS,
        [NodeState(name=f"{name}-n0",
                   allocatable={"cpu": 8000, "memory": 1 << 32, "pods": 110})],
    )
    return EstimatorService(AccurateEstimator(name, cache))


class TestEstimatorPropagation:
    def test_batch_rpc_records_server_span_under_caller_wave(
        self, server_tracer
    ):
        from karmada_tpu.estimator.grpc_transport import (
            EstimatorGrpcServer,
            GrpcEstimatorConnection,
        )
        from karmada_tpu.estimator.service import (
            MaxAvailableReplicasBatchRequest,
        )

        srv = server_tracer(
            "estimator", lambda: EstimatorGrpcServer(_estimator_service())
        )
        port = srv.start()
        conn = GrpcEstimatorConnection(
            "c1", f"127.0.0.1:{port}", timeout_seconds=5.0
        )
        try:
            w = tracer.begin_wave("test")
            with tracer.span("settle"):
                with tracer.span("estimator.refresh"):
                    conn.call(
                        "MaxAvailableReplicasBatch",
                        MaxAvailableReplicasBatchRequest(
                            clusters=["c1"], dims=DIMS,
                            rows=[[1000, 1 << 20, 1]],
                        ),
                    )
            tracer.end_wave()
            client = [
                s for s in tracer.dump(w) if s["name"] == "estimator.rpc"
            ]
            assert len(client) == 1
            assert client[0]["attrs"]["remote"] is True
            assert client[0]["attrs"]["method"] == "MaxAvailableReplicasBatch"
            server = [
                s for s in server_tracer.ring.dump(w)
                if s["name"] == "estimator.serve"
            ]
            assert len(server) == 1
            sspan = server[0]
            assert sspan["wave"] == w
            assert sspan["trace_id"] == client[0]["trace_id"]
            assert sspan["attrs"]["remote_parent"] == client[0]["span_id"]
            assert sspan["attrs"]["caller"] == "plane"
            # the server-side window fits inside the client window
            assert sspan["duration_s"] <= client[0]["duration_s"] + 0.05
        finally:
            conn.close()
            srv.stop()

    def test_unary_fallback_keeps_context_per_attempt(self, server_tracer):
        """The PR 4 negotiated fallback (call_future pipelining) still
        carries context: every per-profile server span lands under the
        caller's wave with a DISTINCT client span as its parent."""
        from karmada_tpu.estimator.grpc_transport import (
            EstimatorGrpcServer,
            GrpcEstimatorConnection,
            RemoteAccurateEstimator,
        )

        srv = server_tracer(
            "estimator",
            lambda: EstimatorGrpcServer(
                _estimator_service(), enable_batch=False
            ),
        )
        port = srv.start()
        conn = GrpcEstimatorConnection(
            "c1", f"127.0.0.1:{port}", timeout_seconds=5.0
        )
        est = RemoteAccurateEstimator("c1", conn, lambda: list(DIMS))
        try:
            w = tracer.begin_wave("test")
            with tracer.span("estimator.refresh"):
                batch = np.asarray(
                    [[1000, 1 << 20, 1], [2000, 1 << 21, 1],
                     [3000, 1 << 22, 1]],
                    np.int64,
                )
                out = est.max_available_replicas(None, batch)
            tracer.end_wave()
            assert conn.supports_batch is False  # negotiated
            assert (np.asarray(out) >= 0).all()
            deadline = time.time() + 5
            while time.time() < deadline:
                server = [
                    s for s in server_tracer.ring.dump(w)
                    if s["name"] == "estimator.serve"
                    and s["attrs"].get("method") == "MaxAvailableReplicas"
                ]
                client = [
                    s for s in tracer.dump(w)
                    if s["name"] == "estimator.rpc"
                    and s["attrs"].get("method") == "MaxAvailableReplicas"
                ]
                if len(server) >= 3 and len(client) >= 3:
                    break
                time.sleep(0.05)  # manual spans close from done callbacks
            assert len(server) == 3 and len(client) == 3
            parents = [s["attrs"]["remote_parent"] for s in server]
            assert sorted(parents) == sorted(
                s["span_id"] for s in client
            ), "each server span re-parents under exactly one client span"
        finally:
            conn.close()
            srv.stop()

    def test_context_survives_reconnect_reprobe(self, server_tracer):
        """A wire failure resets the batch negotiation; the re-probing
        call on the transparently-reconnected channel still carries the
        trace context (the metadata rides every wire attempt, probes
        included)."""
        from karmada_tpu.estimator.grpc_transport import (
            EstimatorGrpcServer,
            GrpcEstimatorConnection,
        )
        from karmada_tpu.estimator.service import GetGenerationsRequest

        srv1 = server_tracer(
            "estimator", lambda: EstimatorGrpcServer(_estimator_service())
        )
        port = srv1.start()
        conn = GrpcEstimatorConnection(
            "c1", f"127.0.0.1:{port}", timeout_seconds=2.0
        )
        try:
            conn.call("GetGenerations", GetGenerationsRequest())
            assert conn.supports_batch is True
            srv1.stop(grace=0)
            with pytest.raises(Exception):
                conn.call("GetGenerations", GetGenerationsRequest())
            assert conn.supports_batch is None  # re-probe armed
            # the server returns at the SAME address (its channel
            # reconnects transparently underneath)
            try:
                srv2 = server_tracer(
                    "estimator",
                    lambda: EstimatorGrpcServer(
                        _estimator_service(), f"127.0.0.1:{port}"
                    ),
                )
            except RuntimeError:
                pytest.skip("port not rebindable on this host")
            srv2.start()
            try:
                w = tracer.begin_wave("test")
                with tracer.span("estimator.refresh"):
                    # the reconnect rides the channel's own backoff —
                    # retry until it lands (each failed attempt is its
                    # own client span; assertions read the LAST pair)
                    deadline = time.time() + 10
                    while True:
                        try:
                            conn.call(
                                "GetGenerations", GetGenerationsRequest()
                            )
                            break
                        except Exception:  # noqa: BLE001 — backoff
                            if time.time() > deadline:
                                raise
                            time.sleep(0.2)
                tracer.end_wave()
                assert conn.supports_batch is True  # re-probed
                client = [
                    s for s in tracer.dump(w)
                    if s["name"] == "estimator.rpc"
                ]
                serve = [
                    s for s in server_tracer.ring.dump(w)
                    if s["name"] == "estimator.serve"
                ]
                assert serve and client
                assert serve[-1]["attrs"]["remote_parent"] == (
                    client[-1]["span_id"]
                )
            finally:
                srv2.stop()
        finally:
            conn.close()

    def test_breaker_open_records_no_rpc_span(self):
        from karmada_tpu.estimator.grpc_transport import (
            GrpcEstimatorConnection,
        )
        from karmada_tpu.estimator.service import GetGenerationsRequest
        from karmada_tpu.utils.backoff import CircuitBreakerOpen

        conn = GrpcEstimatorConnection(
            "c1", "127.0.0.1:1", timeout_seconds=0.2
        )
        try:
            w = tracer.begin_wave("test")
            # trip the breaker on the dead endpoint
            for _ in range(10):
                try:
                    conn.call("GetGenerations", GetGenerationsRequest())
                except Exception:  # noqa: BLE001 — wire failure expected
                    pass
            before = len([
                s for s in tracer.dump(w) if s["name"] == "estimator.rpc"
            ])
            assert conn.breaker.engaged()
            with pytest.raises(CircuitBreakerOpen):
                conn.call("GetGenerations", GetGenerationsRequest())
            tracer.end_wave()
            after = len([
                s for s in tracer.dump(w) if s["name"] == "estimator.rpc"
            ])
            assert after == before, "a fast-failed call is not an RPC span"
        finally:
            conn.close()

    def test_inproc_connection_records_serve_span(self):
        from karmada_tpu.estimator.service import (
            EstimatorConnection,
            MaxAvailableReplicasRequest,
        )

        conn = EstimatorConnection("c1", _estimator_service())
        w = tracer.begin_wave("test")
        with tracer.span("estimator.refresh") as parent:
            conn.call(
                "MaxAvailableReplicas",
                MaxAvailableReplicasRequest(
                    cluster="c1", resource_request={"cpu": 1000}
                ),
            )
        tracer.end_wave()
        serve = [
            s for s in tracer.dump(w) if s["name"] == "estimator.serve"
        ]
        assert len(serve) == 1
        # same process: nests naturally, no remote re-parent marker
        assert serve[0]["parent_id"] == parent.span_id
        assert "caller" not in serve[0]["attrs"]


# --------------------------------------------------------------------------
# solver channel propagation + retry discipline
# --------------------------------------------------------------------------


class TestSolverPropagation:
    def test_retry_spans_are_distinct_parents(self, server_tracer):
        """The FAILED_PRECONDITION re-sync path: each wire attempt is its
        own client span, so the two server-side solver.solve spans (the
        stale one and the retried one) re-parent under DIFFERENT client
        spans — a retried RPC never double-records under one parent."""
        from karmada_tpu.solver import (
            RemoteSolver,
            SolverGrpcServer,
            SolverService,
        )
        from karmada_tpu.utils.builders import synthetic_fleet

        clusters = synthetic_fleet(4)
        srv = server_tracer(
            "solver", lambda: SolverGrpcServer(SolverService())
        )
        port = srv.start()
        client = RemoteSolver(
            f"127.0.0.1:{port}",
            timeout_seconds=60.0,
            cluster_source=lambda: clusters,
        )
        try:
            from karmada_tpu.utils.builders import dynamic_weight_placement
            from karmada_tpu.scheduler import BindingProblem

            problems = [
                BindingProblem(
                    key="b0",
                    placement=dynamic_weight_placement(),
                    replicas=3,
                    requests={"cpu": 100},
                    gvk="apps/v1/Deployment",
                )
            ]
            w = tracer.begin_wave("test")
            with tracer.span("scheduler.pass"):
                # the engine resolves the module-global tracer at call
                # time (function-level imports); in a real sidecar that
                # IS the sidecar's ring — point it there for the call so
                # engine spans land beside the handler spans. The solver
                # CLIENT bound the real global at module import, so its
                # spans keep landing in the plane ring.
                tracing.tracer = server_tracer.ring
                try:
                    results = client.schedule(problems)  # no sync: retry
                finally:
                    tracing.tracer = tracer
            tracer.end_wave()
            assert results and results[0].success
            score_spans = [
                s for s in tracer.dump(w)
                if s["name"] == "solver.rpc"
                and s["attrs"].get("method") == "ScoreAndAssign"
            ]
            sync_spans = [
                s for s in tracer.dump(w)
                if s["name"] == "solver.rpc"
                and s["attrs"].get("method") == "SyncClusters"
            ]
            assert [s["attrs"]["attempt"] for s in score_spans] == [1, 2]
            assert len(sync_spans) == 1
            solve = [
                s for s in server_tracer.ring.dump(w)
                if s["name"] == "solver.solve"
            ]
            sync = [
                s for s in server_tracer.ring.dump(w)
                if s["name"] == "solver.sync"
            ]
            assert len(solve) == 2 and len(sync) == 1
            assert solve[0]["attrs"]["error"] == "stale_snapshot"
            parents = {s["attrs"]["remote_parent"] for s in solve}
            assert parents == {s["span_id"] for s in score_spans}
            assert sync[0]["attrs"]["remote_parent"] == (
                sync_spans[0]["span_id"]
            )
            # engine spans recorded in the sidecar ring nest under the
            # solve handler span — the caller's wave reaches the kernels
            retried = next(
                s for s in solve if "error" not in s["attrs"]
            )
            nested = [
                s for s in server_tracer.ring.dump(w)
                if s["parent_id"] == retried["span_id"]
            ]
            assert nested, "engine spans must nest under solver.solve"
        finally:
            client.close()
            srv.stop()


# --------------------------------------------------------------------------
# bus channel propagation
# --------------------------------------------------------------------------


class TestBusPropagation:
    def test_apply_and_watch_spans(self, server_tracer):
        from karmada_tpu.bus.service import StoreBusServer, StoreReplica
        from karmada_tpu.utils import Store
        from karmada_tpu.utils.builders import new_deployment

        srv = server_tracer("bus", lambda: StoreBusServer(Store()))
        port = srv.start()
        replica = StoreReplica(f"127.0.0.1:{port}")
        replica.start()
        try:
            assert replica.wait_synced(10)
            w = tracer.begin_wave("test")
            with tracer.span("settle"):
                with tracer.span("controller.binding"):
                    replica.apply(new_deployment("d1", replicas=2))
            tracer.end_wave()
            client = [
                s for s in tracer.dump(w) if s["name"] == "bus.rpc"
            ]
            assert len(client) == 1
            assert client[0]["attrs"]["method"] == "Apply"
            server = [
                s for s in server_tracer.ring.dump(w)
                if s["name"] == "bus.apply"
            ]
            assert len(server) == 1
            assert server[0]["attrs"]["remote_parent"] == (
                client[0]["span_id"]
            )
            assert server[0]["attrs"]["caller"] == "plane"
            # the boot Watch replay recorded a bus.watch span (wave 0 —
            # the replica connected outside any wave)
            watch = [
                s for s in server_tracer.ring.dump()
                if s["name"] == "bus.watch"
            ]
            assert watch and watch[0]["attrs"]["replayed"] == 0
        finally:
            replica.close()
            srv.stop()


# --------------------------------------------------------------------------
# the stitcher
# --------------------------------------------------------------------------


class TestStitcher:
    def _plane_and_peer(self, server_tracer):
        """One wave whose estimator RPC crossed into the peer ring."""
        from karmada_tpu.estimator.grpc_transport import (
            EstimatorGrpcServer,
            GrpcEstimatorConnection,
        )
        from karmada_tpu.estimator.service import (
            MaxAvailableReplicasBatchRequest,
        )

        srv = server_tracer(
            "estimator", lambda: EstimatorGrpcServer(_estimator_service())
        )
        port = srv.start()
        conn = GrpcEstimatorConnection(
            "c1", f"127.0.0.1:{port}", timeout_seconds=5.0
        )
        try:
            w = tracer.begin_wave("test")
            with tracer.span("settle"):
                with tracer.span("estimator.refresh"):
                    conn.call(
                        "MaxAvailableReplicasBatch",
                        MaxAvailableReplicasBatchRequest(
                            clusters=["c1"], dims=DIMS,
                            rows=[[1000, 1 << 20, 1]],
                        ),
                    )
            tracer.end_wave()
        finally:
            conn.close()
            srv.stop()
        return w

    def test_stitch_reparents_and_computes_channels(self, server_tracer):
        w = self._plane_and_peer(server_tracer)
        local = trace_debug_doc(tracer_obj=tracer)
        peer = trace_debug_doc(tracer_obj=server_tracer.ring)
        doc = stitch_dumps(local, {"estimator": peer}, wave=w)
        assert doc["procs"] == ["estimator", "plane"]
        assert len(doc["waves"]) == 1
        summary = doc["waves"][0]
        assert summary["stitched"] is True
        assert summary["wave"] == w
        # total is the CALLER-side wall (the settle root) — the
        # re-parented remote span must not inflate it
        settle = next(
            s for s in local["spans"] if s["name"] == "settle"
        )
        assert summary["total_s"] == pytest.approx(
            settle["duration_s"], abs=1e-6
        )
        assert "estimator.serve" in summary["phases"]
        assert set(summary["process_s"]) == {"estimator", "plane"}
        ch = summary["channels"]["estimator"]
        assert ch["rpcs"] == 1
        assert ch["server_s"] > 0
        assert ch["network_s"] >= 0
        assert ch["client_s"] == pytest.approx(
            ch["server_s"] + ch["network_s"], abs=1e-5
        )
        # full attribution: every span's self time telescopes under the
        # root, so coverage stays near 1 even across processes
        assert 0.9 <= summary["coverage"] <= 1.0001

    def test_orphaned_server_span_never_inflates_total(self):
        """A handler span whose client span fell off the ring must not
        become a root (total_s is the caller-side wall)."""
        spans = [
            {"name": "settle", "wave": 1, "span_id": 1, "parent_id": None,
             "trace_id": "t", "duration_s": 1.0, "attrs": {},
             "proc": "plane"},
            {"name": "estimator.serve", "wave": 1, "span_id": 1,
             "parent_id": None, "trace_id": "t", "duration_s": 0.4,
             "attrs": {"remote_parent": 999, "caller": "plane"},
             "proc": "estimator"},
        ]
        summary = tracing.stitch_spans(spans, 1, "t")
        assert summary["total_s"] == pytest.approx(1.0)
        assert summary["phases"]["estimator.serve"] == pytest.approx(0.4)

    def test_wave_summary_stitched_pulls_registered_peers(
        self, server_tracer
    ):
        """wave_summary(stitched=True) fetches every registered peer's
        /debug/traces over HTTP and answers the stitched shape."""
        from karmada_tpu.utils.metrics import MetricsServer

        w = self._plane_and_peer(server_tracer)
        # serve the PEER ring at a metrics port: monkey-build a server
        # whose /debug/traces answers the peer's doc
        peer_doc = trace_debug_doc(tracer_obj=server_tracer.ring)

        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps(peer_doc).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            tracing.register_peer(
                "estimator", f"127.0.0.1:{httpd.server_address[1]}"
            )
            summary = tracer.wave_summary(w, stitched=True)
            assert summary["stitched"] is True
            assert "estimator" in summary["process_s"]
            assert summary["channels"]["estimator"]["rpcs"] == 1
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_unreachable_peer_skipped(self):
        docs = tracing.fetch_peer_dumps({"dead": "127.0.0.1:1"},
                                        timeout=0.2)
        assert docs == {}

    def test_peers_from_env(self, monkeypatch):
        monkeypatch.setenv(
            "KARMADA_TPU_TRACE_PEERS",
            "solver=127.0.0.1:1001, bus=127.0.0.1:1002,bad-entry,=x",
        )
        added = tracing.register_peers_from_env()
        assert added == {
            "solver": "127.0.0.1:1001", "bus": "127.0.0.1:1002",
        }
        assert tracing.peers() == added


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------


@pytest.fixture()
def flight_env(tmp_path, monkeypatch):
    monkeypatch.setenv("KARMADA_TPU_FLIGHT_DIR", str(tmp_path))
    return tmp_path / "flight.jsonl"


class TestFlightRecorder:
    def _wave(self, tr, sleep=0.0):
        w = tr.begin_wave("test")
        with tr.span("settle"):
            if sleep:
                time.sleep(sleep)
        return tr.end_wave(), w

    def test_disarmed_by_default(self, flight_env, monkeypatch):
        monkeypatch.delenv("KARMADA_TPU_TRACE_SLO_SECONDS", raising=False)
        tr = WaveTracer()
        self._wave(tr, sleep=0.01)
        assert not flight_env.exists()

    def test_fires_on_slo_breach(self, flight_env, monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "0.001")
        tr = WaveTracer()
        closed, w = self._wave(tr, sleep=0.02)
        assert closed == w
        records = tracing.load_flight_records(str(flight_env))
        assert len(records) == 1
        rec = records[0]
        assert rec["wave"] == w
        assert any(r.startswith("slo:") for r in rec["reasons"])
        assert rec["spans"] and rec["summary"]["stitched"] is True

    def test_healthy_wave_writes_nothing(self, flight_env, monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "60")
        tr = WaveTracer()
        self._wave(tr)
        assert not flight_env.exists()

    def test_fires_on_degraded_pass(self, flight_env, monkeypatch):
        from karmada_tpu.utils.metrics import degraded_passes

        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "60")
        tr = WaveTracer()
        w = tr.begin_wave("test")
        with tr.span("settle"):
            degraded_passes.inc(channel="estimator")
        tr.end_wave()
        records = tracing.load_flight_records(str(flight_env))
        assert [r["wave"] for r in records] == [w]
        assert records[0]["reasons"] == ["degraded-pass"]
        delta = records[0]["metrics_delta"]
        assert "karmada_tpu_degraded_passes_total" in delta

    def test_fires_on_breaker_transition_span(self, flight_env,
                                              monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "60")
        tr = WaveTracer()
        w = tr.begin_wave("test")
        with tr.span("settle"):
            tr.record("channel.breaker", 0.0, channel="solver",
                      from_state="closed", to_state="open")
        tr.end_wave()
        records = tracing.load_flight_records(str(flight_env))
        assert records[0]["wave"] == w
        assert "breaker-transition" in records[0]["reasons"]

    def test_disk_ring_cap(self, flight_env, monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "0.0001")
        monkeypatch.setenv("KARMADA_TPU_FLIGHT_CAP", "2")
        tr = WaveTracer()
        waves = [self._wave(tr, sleep=0.002)[0] for _ in range(4)]
        records = tracing.load_flight_records(str(flight_env))
        assert [r["wave"] for r in records] == waves[-2:]

    def test_analyze_rerenders_identically(self, flight_env, monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "0.001")
        tr = WaveTracer()
        w = tr.begin_wave("test")
        with tr.span("settle"):
            with tr.span("scheduler.pass"):
                time.sleep(0.01)
        tr.end_wave()
        from karmada_tpu.cli import cmd_trace_analyze

        doc = cmd_trace_analyze(str(flight_env), wave=w)
        assert doc["identical"] is True
        assert doc["wave"] == w
        assert "scheduler.pass" in doc["summary"]["phases"]
        assert f"wave {w}" in doc["table"]

    def test_recorder_failure_never_aborts_the_wave(self, monkeypatch):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "0.0001")
        monkeypatch.setenv("KARMADA_TPU_FLIGHT_DIR", "/dev/null/nope")
        tr = WaveTracer()
        closed, w = self._wave(tr, sleep=0.002)
        assert closed == w  # no raise


# --------------------------------------------------------------------------
# CLI surfaces
# --------------------------------------------------------------------------


class TestCliTrace:
    def test_dump_stitch_with_explicit_peer(self, server_tracer):
        from karmada_tpu.cli import cmd_trace_dump

        helper = TestStitcher()
        w = helper._plane_and_peer(server_tracer)
        peer_doc = trace_debug_doc(tracer_obj=server_tracer.ring)

        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps(peer_doc).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            doc = cmd_trace_dump(
                stitch=True, wave=w,
                peers=f"estimator=127.0.0.1:{httpd.server_address[1]}",
            )
            assert doc["procs"] == ["estimator", "plane"]
            assert doc["waves"][0]["channels"]["estimator"]["rpcs"] == 1
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_dump_stitch_no_peers_is_local_only(self):
        from karmada_tpu.cli import cmd_trace_dump

        w = tracer.begin_wave("test")
        with tracer.span("settle"):
            pass
        tracer.end_wave()
        doc = cmd_trace_dump(stitch=True, wave=w)
        assert doc["procs"] == ["plane"]
        assert doc["waves"][0]["stitched"] is True

    def test_analyze_missing_record_errors(self, tmp_path):
        from karmada_tpu.cli import cmd_trace_analyze

        empty = tmp_path / "flight.jsonl"
        empty.write_text("")
        with pytest.raises(ValueError):
            cmd_trace_analyze(str(empty))

    def test_cli_main_trace_analyze(self, tmp_path, monkeypatch, capsys):
        monkeypatch.setenv("KARMADA_TPU_TRACE_SLO_SECONDS", "0.0001")
        monkeypatch.setenv("KARMADA_TPU_FLIGHT_DIR", str(tmp_path))
        tr = WaveTracer()
        tr.begin_wave("test")
        with tr.span("settle"):
            time.sleep(0.002)
        tr.end_wave()
        from karmada_tpu.cli import main

        rc = main(["trace", "analyze", str(tmp_path / "flight.jsonl")])
        assert rc == 0
        out = capsys.readouterr().out
        assert json.loads(out)["identical"] is True
