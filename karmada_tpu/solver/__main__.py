"""Solver sidecar process entry: ``python -m karmada_tpu.solver``."""

from __future__ import annotations

import argparse
import sys

from .service import SolverGrpcServer, SolverService


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="karmada-tpu solver sidecar")
    p.add_argument("--address", default="127.0.0.1:0")
    p.add_argument("--server-cert", default="", help="PEM file (TLS)")
    p.add_argument("--server-key", default="", help="PEM file (TLS)")
    p.add_argument("--client-ca", default="", help="PEM file (mTLS client auth)")
    p.add_argument(
        "--report-backend", action="store_true",
        help="initialise the jax backend at boot and print 'solver "
        "backend <platform>' after the port lines — the orchestrator "
        "scrapes it and refuses a sidecar that runs on another platform "
        "than it was given. A backend that cannot initialise (no such "
        "platform, chip owned by another process) ends the process with "
        "its traceback before any port is bound",
    )
    p.add_argument(
        "--warmup-manifest", default=None,
        help="trace-manifest path: AOT-prewarm the engine's XLA traces "
        "from it after backend init (off the serving path) and record "
        "fresh traces back into it, so a sidecar restart's first "
        "ScoreAndAssign wave runs only already-compiled traces "
        "(default: $KARMADA_TPU_TRACE_MANIFEST; '' disables)",
    )
    p.add_argument(
        "--metrics-port", default=None,
        help="serve /metrics + /healthz + /debug/traces on this port or HOST:PORT "
        "(0 = ephemeral, printed as 'metrics listening on port N'; "
        "default: $KARMADA_TPU_METRICS_PORT, empty = disabled)",
    )
    p.add_argument(
        "--estimator", action="append", default=[],
        help="NAME=HOST:PORT of an accurate-estimator server for cluster "
        "NAME (repeatable; same HOST:PORT shares one channel): the "
        "sidecar's engines min-merge live estimator answers into "
        "availability exactly like the in-proc plane does (localup serve "
        "--estimator) — the estimator channel moves WITH the engine when "
        "scheduling moves into the sidecar",
    )
    args = p.parse_args(argv)
    # chaos: arm deterministic fault injection from the environment
    # (KARMADA_TPU_FAULT_SPEC; disarmed when empty — zero overhead)
    from ..utils.faultinject import arm_from_env
    from ..utils.tracing import register_peers_from_env, tracer

    arm_from_env()
    # cross-process tracing: this process's spans export as proc="solver"
    # (the stitcher keys on it) and any configured peers register for
    # stitched dumps taken FROM this process
    tracer.set_process("solver")
    register_peers_from_env()

    def read(path):
        return open(path, "rb").read() if path else None

    # SIGTERM takes the interpreter's normal exit path (atexit hooks,
    # buffered output, the accelerator client's own teardown) instead of
    # the default-action kill
    import signal as _signal

    _signal.signal(_signal.SIGTERM, lambda s, f: sys.exit(0))

    backend = ""
    if args.report_backend:
        # before anything binds: a failed init is a plain non-zero exit
        # whose traceback the orchestrator's scrape shows
        import jax

        backend = jax.devices()[0].platform

    import os

    from ..scheduler.prewarm import resolve_boot_manifest
    from ..utils.compilecache import MANIFEST_ENV

    # flag absent (None) falls back to the env default; an EXPLICIT
    # --warmup-manifest '' disables even with the env var set (the
    # opt-out the help text promises). Exported so an opt-out also sticks
    # for engines this process builds without an explicit manifest.
    manifest_path = resolve_boot_manifest(args.warmup_manifest)
    os.environ[MANIFEST_ENV] = manifest_path
    if manifest_path:
        # the sidecar owns the engine (and with it the accelerator's trace
        # set): its engines record fresh traces into the manifest and —
        # once the prewarm below ran — seed their new-trace ledger from it
        from ..scheduler import TensorScheduler
        from ..scheduler.prewarm import TraceManifest

        manifest = TraceManifest(manifest_path)

        def base_factory(snap):
            return TensorScheduler(snap, trace_manifest=manifest)
    else:
        from ..scheduler import TensorScheduler

        manifest = None
        base_factory = TensorScheduler

    est_registry = None
    if args.estimator:
        # estimator-aware sidecar: register a RemoteAccurateEstimator per
        # named cluster (channels shared per target) and fold the live
        # answers into every engine this service builds — the same
        # min-merge the in-proc plane applies, now ON the process that
        # actually solves, so the scheduler->solver->estimator chain is
        # one stitched trace
        from ..estimator.accurate import EstimatorRegistry
        from ..estimator.grpc_transport import GrpcEstimatorConnection

        est_registry = EstimatorRegistry()
        svc_cell: list = []  # filled after SolverService construction

        def engine_dims():
            return list(svc_cell[0]._engine.snapshot.dims)

        conns: dict = {}
        from ..estimator.grpc_transport import RemoteAccurateEstimator

        for spec in args.estimator:
            name, _, target = spec.partition("=")
            if not name or not target:
                p.error(f"--estimator wants NAME=HOST:PORT, got {spec!r}")
            conn = conns.get(target)
            if conn is None:
                conn = GrpcEstimatorConnection(name, target)
                conns[target] = conn
            est_registry.register(
                RemoteAccurateEstimator(name, conn, engine_dims)
            )

        def engine_factory(snap):
            eng = base_factory(snap)
            eng.extra_estimators = [
                est_registry.make_batch_estimator(list(snap.names))
            ]
            return eng
    else:
        engine_factory = base_factory

    service = SolverService(engine_factory=engine_factory)
    if est_registry is not None:
        svc_cell.append(service)
        # the sidecar has no member-event channel to invalidate the
        # registry, so every solve revalidates it generation-gated (the
        # PR 4 contract): one GetGenerations ping per server per pass,
        # re-fetch only for clusters whose snapshot actually moved — a
        # memoized answer can never go stale across passes
        _score = service.score_and_assign

        def score_with_revalidate(request):
            est_registry.invalidate()
            return _score(request)

        service.score_and_assign = score_with_revalidate

    server = SolverGrpcServer(
        service,
        args.address,
        server_cert=read(args.server_cert),
        server_key=read(args.server_key),
        client_ca=read(args.client_ca),
    )
    port = server.start()
    # the parent process scrapes this line to learn the bound port
    print(f"solver listening on port {port}", flush=True)
    from ..utils.metrics import serve_process_metrics

    # AFTER the gRPC port line (orchestrators scrape the first
    # "port (\d+)" match)
    metrics = serve_process_metrics(args.metrics_port)
    if metrics is not None:
        print(f"metrics listening on port {metrics.port}", flush=True)
    if args.report_backend:
        print(f"solver backend {backend}", flush=True)
    # scheduling-mesh report: when the env requests a mesh
    # (KARMADA_TPU_MESH_DEVICES), resolve and print its shape so the
    # orchestrator (and `karmadactl-tpu trace dump`) can tell a
    # single-chip from an 8-chip plane. Env-gated: without the knob this
    # prints nothing and never touches the backend.
    if os.environ.get("KARMADA_TPU_MESH_DEVICES", "").strip() not in (
        "", "0", "1"
    ):
        from ..parallel.mesh import mesh_shape, resolve_mesh

        try:
            shape = mesh_shape(resolve_mesh(None))
        except Exception as exc:  # noqa: BLE001 — report, then let the
            # first engine construction fail loudly with the same error
            print(f"solver mesh error: {exc}", flush=True)
        else:
            axes = " ".join(f"{n}={s}" for n, s in (shape or ()))
            print(f"solver mesh {axes or 'single-device'}", flush=True)
    if manifest is not None:
        # prewarm AFTER the port/backend lines the orchestrator scrapes:
        # compiles run off the serving path (the plane connects and syncs
        # while this proceeds; the gRPC executor serves concurrently).
        # warmup() also drops the persistence threshold to 0 so every
        # warmed trace lands in the persistent cache.
        from ..scheduler.prewarm import warmup

        stats = warmup(manifest.path)
        print(
            f"solver prewarm {stats['compiled']}/{stats['specs']} traces "
            f"in {stats['seconds']:.1f}s",
            flush=True,
        )
    try:
        server.wait()
    except KeyboardInterrupt:
        server.stop()
        sys.exit(0)


if __name__ == "__main__":
    main()
