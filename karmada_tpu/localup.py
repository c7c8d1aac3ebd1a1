"""Multi-process local-up: the hack/local-up-karmada.sh analogue.

Ref: hack/local-up-karmada.sh:33-46 boots a full multi-process Karmada
(apiserver + controller-manager + scheduler + webhook + agent in kind
clusters); hack/run-e2e.sh:44-56 then drives 36 e2e suites against it.

This module composes the TPU-native plane the same way, as REAL OS
processes wired only by network surfaces:

- the PLANE process (``python -m karmada_tpu.localup serve``) runs the
  store + controller fleet + scheduler and serves three network surfaces:
  the store bus (gRPC watch/apply), the cluster proxy (HTTP), and
  /metrics (Prometheus text);
- a SOLVER sidecar process (``python -m karmada_tpu.solver``) owns the
  Score/Assign engine; the plane routes scheduling over gRPC with
  snapshot-version fencing;
- an ESTIMATOR server process (``python -m karmada_tpu.estimator``) per
  designated member answers MaxAvailableReplicas over gRPC;
- a pull-mode AGENT process (``python -m karmada_tpu.bus.agent``) mirrors
  the plane over the bus and drives its member cluster.

``LocalUp`` is the orchestrator: it spawns the children, scrapes their
ports, and exposes the endpoints — used by the CLI (``local-up
--processes``) and by tests/test_localup_processes.py, which drives the
quickstart through the network surfaces only.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time
from typing import Optional


def spawn_child(
    cmd: list[str], platform: str = "cpu", extra_env: dict | None = None
) -> subprocess.Popen:
    """Spawn a component child process: ``platform`` becomes its
    ``JAX_PLATFORMS`` — the whole backend-selection mechanism (default
    CPU: control-plane components never touch the accelerator) — and the
    package is importable regardless of the caller's cwd. Shared by
    LocalUp and the process operator — one copy of the env construction.
    ``extra_env`` overlays the inherited environment (the orchestrator
    hands the plane child its peers' trace endpoints this way).

    One process owns a chip at a time: exactly one component per chip may
    run with a non-cpu platform (deployment-wise that is the solver
    sidecar — the "dedicate a chip to scheduling" shape in
    docs/OPERATIONS.md), and the caller must not have initialised that
    backend itself."""
    env = dict(os.environ, JAX_PLATFORMS=platform, **(extra_env or {}))
    pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (
        pkg_parent + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH")
        else pkg_parent
    )
    return subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env,
    )


def scrape_line(proc: subprocess.Popen, pattern: str, timeout: float = 240.0) -> str:
    """First regex group of the first stdout line matching ``pattern``.

    select()-gated so a child that hangs BEFORE printing (import stall,
    bind wait) raises after ``timeout`` instead of blocking forever; a
    child that dies mid-startup raises immediately — with its recent
    output in the error, so startup failures are diagnosable from the
    orchestrator's traceback alone.

    Reads the pipe raw and keeps the unconsumed lines on ``proc``: a
    buffered ``readline()`` may swallow several lines in one read, after
    which select() never reports the ones left in its buffer — a child
    that prints its startup lines back to back would look hung."""
    import collections
    import select

    state = vars(proc)  # unconsumed output survives between calls
    pending = state.setdefault("_scrape_pending", collections.deque())
    tail: collections.deque = collections.deque(maxlen=15)
    fd = proc.stdout.fileno()

    def pump(block: float):
        """Move what the child wrote into ``pending``: bytes read, 0 at
        EOF, None when nothing arrived within ``block`` seconds."""
        ready, _, _ = select.select([fd], [], [], block)
        if not ready:
            return None
        chunk = os.read(fd, 1 << 16)
        data = state.pop("_scrape_partial", b"") + chunk
        *lines, partial = data.split(b"\n")
        pending.extend(ln.decode(errors="replace") for ln in lines)
        if chunk:
            state["_scrape_partial"] = partial
        elif partial:
            pending.append(partial.decode(errors="replace"))
        return len(chunk)

    def die(reason: str) -> None:
        if proc.poll() is not None:
            while pump(0):  # drain what is left: the traceback is the
                pass  # diagnosis
            tail.extend(pending)
            pending.clear()
        out = "\n".join(f"    | {ln.rstrip()}" for ln in tail)
        raise RuntimeError(
            f"{reason} (cmd: {' '.join(proc.args[:6])}...)\n"
            f"  recent child output:\n{out or '    | <none>'}"
        )

    deadline = time.time() + timeout
    while True:
        while pending:
            line = pending.popleft()
            tail.append(line)
            m = re.search(pattern, line)
            if m:
                return m.group(1)
        remaining = deadline - time.time()
        if remaining <= 0:
            die(f"no line matching {pattern!r} within {timeout}s")
        got = pump(min(remaining, 0.5))
        if not pending and proc.poll() is not None:
            die(f"child exited rc={proc.returncode} during startup")
        if got == 0:
            time.sleep(0.05)  # stdout closed but child alive: avoid spin


def _scrape_port(proc: subprocess.Popen, pattern: str, timeout: float = 240.0) -> int:
    return int(scrape_line(proc, pattern, timeout))


def drain_output(proc: subprocess.Popen) -> None:
    """Keep reading, and dropping, what the child writes from here on, on a
    daemon thread. ``spawn_child`` hands the child one pipe for stdout and
    stderr; once the lines a launcher waits for are scraped nobody reads
    it, and a child that has written it full (64 KiB: XLA's warnings on
    loading a compile cache alone do that) blocks in its next write, in the
    middle of whatever it was serving."""
    import threading

    fd = proc.stdout.fileno()

    def pump() -> None:
        try:
            while os.read(fd, 1 << 16):
                pass
        except (OSError, ValueError):
            pass  # the pipe closed under us: the child is gone

    threading.Thread(target=pump, daemon=True).start()


def scrape_solver_backend(
    proc: subprocess.Popen, platform: str, timeout: float = 120.0
) -> str:
    """The backend a ``--report-backend`` solver sidecar resolved, held to
    the platform it was spawned with: a sidecar asked for ``tpu`` that
    runs on anything else is refused, never recorded and carried on with
    (a failed backend init ends the child, which ``scrape_line`` raises
    on with the child's traceback)."""
    backend = scrape_line(proc, r"solver backend (\S+)", timeout)
    wanted = platform.split(",")[0]
    if backend != wanted:
        raise RuntimeError(
            f"solver sidecar was spawned with JAX_PLATFORMS={platform} "
            f"but runs on {backend!r}"
        )
    return backend


# --------------------------------------------------------------------------
# the plane process
# --------------------------------------------------------------------------


def serve_plane_replica(args) -> None:
    """HA plane replica (the reference's --leader-elect active-standby
    shape, cmd/scheduler/app/options/options.go:130-165): the controller
    fleet runs over a bus StoreReplica of an EXTERNAL store process
    (python -m karmada_tpu.bus), and only the Lease-elected leader
    reconciles. Standbys stay warm — their mirrors track every event and
    their workqueues accumulate keys — so takeover is one settle away.
    No double-scheduling: leadership is CAS-exclusive per tick, and the
    scheduler's observed-generation guard makes a raced duplicate
    reconcile idempotent."""
    import os

    from .bus.agent import ReplicaStoreFacade
    from .bus.service import StoreReplica
    from .controlplane import ControlPlane
    from .utils.builders import new_cluster
    from .utils.leaderelect import LeaderElector
    from .utils.member import MemberCluster
    from .utils.metrics import MetricsServer
    from .utils.net import parse_hostport as addr
    from .utils.tracing import register_peers_from_env, tracer

    tracer.set_process("plane")
    register_peers_from_env()

    replica = StoreReplica(args.connect_bus)
    replica.start()
    if not replica.wait_synced(30):
        print("error: bus replica failed to sync", file=sys.stderr)
        sys.exit(2)
    facade = ReplicaStoreFacade(replica)
    cp = ControlPlane(
        store=facade,
        enable_descheduler=args.descheduler,
        lease_grace_seconds=args.lease_grace or None,
    )
    from .utils.store import ConflictError

    for name in args.pull:
        # every replica registers the local inventory shell + status
        # watch; the Cluster OBJECT is created create-only (expected_rv=0)
        # so two concurrently booting replicas cannot clobber the agent's
        # already-written status through their async mirrors (a check-
        # then-act on the mirror races; the CAS loses cleanly instead)
        member = MemberCluster(name)
        cp.members.register(member)
        cp.work_status_controller.watch_member(member)
        if facade.get("Cluster", name) is None:
            cluster = new_cluster(name, cpu="100", memory="200Gi")
            cluster.spec.sync_mode = "Pull"
            try:
                facade.apply(cluster, expected_rv=0)
            except ConflictError:
                pass  # a peer replica won the create
    # HA standbys prewarm at boot: a takeover's first scheduling wave is
    # exactly the cold wave the manifest exists to kill — a standby that
    # compiles AFTER winning the lease serves its first storm cold.
    from .scheduler.prewarm import resolve_boot_manifest
    from .utils.compilecache import MANIFEST_ENV

    manifest_path = resolve_boot_manifest(args.warmup_manifest)
    # export the resolved path (including an explicit "" opt-out): the
    # scheduler controller builds its engine lazily and resolves the
    # manifest from this env var — without it the replica would prewarm
    # but never seed its trace ledger or record fresh traces back
    os.environ[MANIFEST_ENV] = manifest_path
    if manifest_path:
        from .scheduler.prewarm import warmup

        stats = warmup(manifest_path)
        print(
            f"# replica prewarm: {stats['compiled']}/{stats['specs']} "
            f"traces in {stats['seconds']:.1f}s",
            file=sys.stderr,
        )
    cp.runtime.realtime = True
    metrics = MetricsServer(address=addr(args.metrics_address))
    metrics_port = metrics.start()
    identity = args.identity or f"plane-{os.getpid()}"
    elector = LeaderElector(
        facade,
        "karmada-plane",
        identity,
        lease_duration=args.lease_duration,
        renew_deadline=args.renew_deadline,
        on_started_leading=lambda: print(
            json.dumps({"leading": identity}), flush=True
        ),
        on_stopped_leading=lambda: print(
            json.dumps({"standby": identity}), flush=True
        ),
    )
    # renewals must survive long settles (client-go renews on its own
    # goroutine; this runtime is cooperative, so renewal rides the drain
    # loop via the heartbeat seam), throttled to lease/5 so neither the
    # settle loop nor the serve loop hammers the bus with CAS writes —
    # and the moment leadership is lost mid-settle, the heartbeat's False
    # aborts the drain so a deposed leader stops writing immediately
    last_tick = [0.0]

    def renew_tick() -> bool:
        now = time.time()
        if now - last_tick[0] >= args.lease_duration / 5:
            last_tick[0] = now
            elector.tick()
        return elector.is_leader

    cp.runtime.heartbeat = renew_tick
    print(
        json.dumps({"metrics": metrics_port, "identity": identity}),
        flush=True,
    )

    stop = [False]

    def on_term(signum, frame):
        stop[0] = True

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    try:
        while not stop[0]:
            leading = renew_tick()
            if leading:
                cp.settle()
                due = cp.runtime.next_due()
                time.sleep(
                    max(0.001, min(args.loop_interval, due))
                    if due is not None
                    else args.loop_interval
                )
            else:
                time.sleep(args.loop_interval)
    finally:
        elector.release()
        metrics.stop()
        replica.close()


def serve_plane(args) -> None:
    """Run the control plane + its network surfaces until SIGTERM."""
    if args.connect_bus:
        return serve_plane_replica(args)
    from .bus.service import StoreBusServer
    from .cli import cmd_init, cmd_join
    from .controlplane import ControlPlane  # noqa: F401 (docs)
    from .search.proxyserver import ClusterProxyServer
    from .utils.builders import new_cluster
    from .utils.metrics import MetricsServer
    from .utils.tracing import register_peers_from_env, tracer

    tracer.set_process("plane")
    register_peers_from_env()

    if args.feature_gates:
        from .utils.features import feature_gate

        for spec in args.feature_gates.split(","):
            name, _, val = spec.partition("=")
            feature_gate.set(name.strip(), val.strip().lower() in ("1", "true", ""))

    admission_kw = {}
    if args.admission:
        # out-of-process TLS admission: every store write round-trips the
        # webhook process (cmd/webhook deployment shape)
        from .webhook.server import RemoteAdmission

        ca = open(args.admission_ca, "rb").read() if args.admission_ca else None
        remote = RemoteAdmission(args.admission, ca_bundle=ca)
        admission_kw = {
            "admission_override": remote.admit,
            "delete_admission_override": remote.admit_delete,
        }

    solver = None
    if args.solver:
        # comma-separated targets = HA solver replicas: the plane sticks
        # to the active one and fails over on transport errors
        targets = [t for t in args.solver.split(",") if t]
        if not targets:
            print("error: --solver given but no targets parsed",
                  file=sys.stderr)
            sys.exit(2)
        if len(targets) > 1:
            from .solver.client import HASolver

            solver = HASolver(targets)
        else:
            from .solver.client import RemoteSolver

            solver = RemoteSolver(targets[0])
    cp = cmd_init(solver=solver, enable_descheduler=args.descheduler,
                  lease_grace_seconds=args.lease_grace or None,
                  **admission_kw)
    if args.state_file and os.path.exists(args.state_file):
        # etcd-persistence analogue: a restarted plane restores the store
        # snapshot its predecessor checkpointed on shutdown, so operator
        # upgrades don't wipe control-plane state
        restored = cp.store.restore(args.state_file)
        print(f"# restored {restored} objects from {args.state_file}",
              file=sys.stderr)
    for i in range(1, args.members + 1):
        cmd_join(cp, f"member{i}", cpu="100", memory="200Gi")
    for name in args.pull:
        cluster = new_cluster(name, cpu="100", memory="200Gi")
        cluster.spec.sync_mode = "Pull"
        cp.join_cluster(cluster, remote_agent=True)

    # boot-phase prewarm: replay the trace manifest through AOT compile
    # BEFORE the first settle, so the plane's first scheduling wave (the
    # cold wave a restart/HA-failover pays) runs only already-compiled
    # traces. Only meaningful when the plane runs the in-proc engine —
    # with a solver sidecar the sidecar prewarms itself (its own
    # --warmup-manifest).
    from .scheduler.prewarm import resolve_boot_manifest
    from .utils.compilecache import MANIFEST_ENV

    manifest_path = resolve_boot_manifest(args.warmup_manifest)
    # export the resolved path (including an explicit "" opt-out): the
    # scheduler controller builds its engine lazily and resolves the
    # manifest from this env var — without it the plane would prewarm but
    # never seed its trace ledger (first pass still new_trace=True) or
    # record fresh traces back into the manifest
    os.environ[MANIFEST_ENV] = manifest_path
    if manifest_path and not solver:
        from .scheduler.prewarm import warmup

        stats = warmup(manifest_path)
        print(
            f"# plane prewarm: {stats['compiled']}/{stats['specs']} traces "
            f"in {stats['seconds']:.1f}s from {manifest_path}",
            file=sys.stderr,
        )

    # remote estimator registrations: NAME=HOST:PORT
    if args.estimator:
        from .estimator.grpc_transport import (
            GrpcEstimatorConnection,
            RemoteAccurateEstimator,
        )

        for spec in args.estimator:
            name, _, target = spec.partition("=")
            conn = GrpcEstimatorConnection(name, target)
            cp.estimators.register(
                RemoteAccurateEstimator(
                    name, conn, lambda: cp.scheduler.snapshot.dims
                )
            )
        names = sorted(cp.members.names())
        cp.scheduler.extra_estimators = [
            cp.estimators.make_batch_estimator(names)
        ]

    bus = StoreBusServer(cp.store, args.bus_address)
    bus_port = bus.start()

    from .utils.net import parse_hostport as addr

    proxy = ClusterProxyServer(
        cp.members, addr(args.proxy_address),
        tokens={"admin-token": ("admin", ["system:masters"])},
    )
    proxy_port = proxy.start()
    metrics = MetricsServer(address=addr(args.metrics_address))
    metrics_port = metrics.start()
    # serve mode runs against the wall clock: reconcile failures back off
    # exponentially (workqueue DefaultControllerRateLimiter discipline)
    # instead of burning 16 hot-loop retries inside one settle call.
    # Set BEFORE the boot settle — a member that is slow to come up must
    # park its keys for the serve loop, not burn the drop budget at boot.
    cp.runtime.realtime = True
    cp.settle()
    print(
        json.dumps(
            {
                "bus": bus_port,
                "proxy": proxy_port,
                "metrics": metrics_port,
                "clusters": sorted(c.name for c in cp.store.list("Cluster")),
            }
        ),
        flush=True,
    )

    stop = [False]

    def on_term(signum, frame):
        stop[0] = True

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    last_ckpt = time.time()
    last_ckpt_rv = -1
    try:
        while not stop[0]:
            cp.settle()
            if (
                args.state_file
                and args.checkpoint_interval > 0
                and time.time() - last_ckpt >= args.checkpoint_interval
            ):
                # periodic durability: a SIGKILLed plane restarts from the
                # last interval snapshot, not from empty (etcd analogue).
                # Skipped while the store rv is unchanged — an idle plane
                # must not re-serialize its whole store every interval.
                rv = cp.store.rv
                if rv != last_ckpt_rv:
                    cp.store.checkpoint(args.state_file)
                    last_ckpt_rv = rv
                last_ckpt = time.time()
            due = cp.runtime.next_due()
            time.sleep(
                max(0.001, min(args.loop_interval, due))
                if due is not None
                else args.loop_interval
            )
    finally:
        if args.state_file:
            saved = cp.store.checkpoint(args.state_file)
            print(f"# checkpointed {saved} objects to {args.state_file}",
                  file=sys.stderr)
        metrics.stop()
        proxy.stop()
        bus.stop()


# --------------------------------------------------------------------------
# the orchestrator
# --------------------------------------------------------------------------


class LocalUp:
    """Spawn the full multi-process deployment; context-manager teardown.

    Children: solver sidecar, one estimator (member1), the plane (bus +
    proxy + metrics), one pull agent. All wiring is host:port — nothing
    shares memory with anything else."""

    def __init__(
        self,
        members: int = 2,
        pull: tuple[str, ...] = ("pull1",),
        with_solver: bool = True,
        with_estimator: bool = True,
        descheduler: bool = False,
        lease_grace: float = 0.0,
        feature_gates: str = "Failover=true",
        solver_platform: str = "cpu",
        warmup_manifest: str | None = None,
    ):
        self.lease_grace = lease_grace
        self.feature_gates = feature_gates
        # trace-manifest path handed to the scheduling-owning child (the
        # solver sidecar when present, else the plane): that child AOT-
        # prewarms from it at boot and records fresh traces back into it
        self.warmup_manifest = warmup_manifest
        self.members = members
        self.pull = pull
        self.with_solver = with_solver
        self.with_estimator = with_estimator
        self.descheduler = descheduler
        # per-component platform policy: only the solver sidecar may own
        # the chip (one process per chip); everything else is CPU
        self.solver_platform = solver_platform
        self.solver_backend = ""  # scraped from the sidecar at startup
        self.procs: dict[str, subprocess.Popen] = {}
        self.endpoints: dict[str, int] = {}

    def _spawn(
        self, name: str, cmd: list[str], platform: str = "cpu",
        extra_env: dict | None = None,
    ) -> subprocess.Popen:
        proc = spawn_child(cmd, platform=platform, extra_env=extra_env)
        self.procs[name] = proc
        return proc

    def __enter__(self) -> "LocalUp":
        py = sys.executable
        try:
            if self.with_solver:
                solver_cmd = [
                    py, "-m", "karmada_tpu.solver", "--address",
                    "127.0.0.1:0", "--report-backend", "--metrics-port", "0",
                ]
                if self.warmup_manifest is not None:
                    # an explicit "" propagates as the child's opt-out
                    # (overrides an inherited KARMADA_TPU_TRACE_MANIFEST)
                    solver_cmd += ["--warmup-manifest", self.warmup_manifest]
                p = self._spawn(
                    "solver", solver_cmd, platform=self.solver_platform,
                )
                self.endpoints["solver"] = _scrape_port(p, r"port (\d+)")
                self.endpoints["solver_metrics"] = _scrape_port(
                    p, r"metrics listening on port (\d+)"
                )
                self.solver_backend = scrape_solver_backend(
                    p, self.solver_platform
                )
            if self.with_estimator:
                p = self._spawn(
                    "estimator",
                    [py, "-m", "karmada_tpu.estimator", "--cluster", "member1",
                     "--address", "127.0.0.1:0", "--metrics-port", "0"],
                )
                self.endpoints["estimator"] = _scrape_port(p, r"port (\d+)")
                self.endpoints["estimator_metrics"] = _scrape_port(
                    p, r"metrics listening on port (\d+)"
                )

            # the plane child learns where to stitch cross-process traces
            # from: every spawned peer's metrics endpoint, exported as
            # KARMADA_TPU_TRACE_PEERS (utils.tracing boot hook)
            peer_specs = [
                f"{name.removesuffix('_metrics')}=127.0.0.1:{port}"
                for name, port in self.endpoints.items()
                if name.endswith("_metrics")
            ]
            plane_env = (
                {"KARMADA_TPU_TRACE_PEERS": ",".join(peer_specs)}
                if peer_specs
                else None
            )

            plane_cmd = [
                py, "-m", "karmada_tpu.localup", "serve",
                "--members", str(self.members),
            ]
            for name in self.pull:
                plane_cmd += ["--pull", name]
            if self.with_solver:
                plane_cmd += ["--solver", f"127.0.0.1:{self.endpoints['solver']}"]
            if self.with_estimator:
                plane_cmd += [
                    "--estimator", f"member1=127.0.0.1:{self.endpoints['estimator']}"
                ]
            if self.descheduler:
                plane_cmd += ["--descheduler"]
            if self.lease_grace:
                plane_cmd += ["--lease-grace", str(self.lease_grace)]
            if self.feature_gates:
                plane_cmd += ["--feature-gates", self.feature_gates]
            if self.warmup_manifest is not None:
                plane_cmd += ["--warmup-manifest", self.warmup_manifest]
            p = self._spawn("plane", plane_cmd, extra_env=plane_env)
            deadline = time.time() + 240
            while time.time() < deadline:
                line = p.stdout.readline()
                if line.startswith("{"):
                    info = json.loads(line)
                    self.endpoints.update(
                        bus=info["bus"], proxy=info["proxy"], metrics=info["metrics"]
                    )
                    self.clusters = info["clusters"]
                    break
                if p.poll() is not None:
                    raise RuntimeError(f"plane exited rc={p.returncode}")
            else:
                raise RuntimeError("plane never printed its endpoints")

            for name in self.pull:
                self._spawn(
                    f"agent-{name}",
                    [py, "-m", "karmada_tpu.bus.agent",
                     "--target", f"127.0.0.1:{self.endpoints['bus']}",
                     "--cluster", name],
                )
            return self
        except Exception:
            self.__exit__(None, None, None)
            raise

    def __exit__(self, *exc) -> None:
        for proc in reversed(list(self.procs.values())):
            if proc.poll() is None:
                proc.terminate()
        for proc in self.procs.values():
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5)

    def kill(self, name: str) -> None:
        """Fault injection: hard-kill one component process."""
        proc = self.procs[name]
        proc.kill()
        proc.wait(timeout=5)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    sv = sub.add_parser("serve", help="run the plane process (internal)")
    sv.add_argument("--members", type=int, default=2)
    sv.add_argument("--pull", action="append", default=[])
    sv.add_argument(
        "--solver", default="",
        help="solver sidecar host:port (comma-separated = HA replicas "
        "with client failover)",
    )
    sv.add_argument("--estimator", action="append", default=[])
    sv.add_argument("--bus-address", default="127.0.0.1:0")
    sv.add_argument("--descheduler", action="store_true")
    sv.add_argument("--loop-interval", type=float, default=0.05)
    sv.add_argument("--lease-grace", type=float, default=0.0)
    sv.add_argument("--feature-gates", default="",
                    help="comma list NAME=true|false (pkg/features)")
    sv.add_argument("--admission", default="",
                    help="external admission webhook URL (https://.../admit)")
    sv.add_argument("--admission-ca", default="",
                    help="PEM CA bundle for the admission webhook")
    sv.add_argument("--state-file", default="",
                    help="checkpoint/restore path for the store (the etcd "
                    "persistence analogue across plane restarts)")
    sv.add_argument("--checkpoint-interval", type=float, default=15.0,
                    help="periodic store checkpoint seconds (0 = only on "
                    "shutdown); bounds data loss on a hard kill")
    sv.add_argument("--proxy-address", default="127.0.0.1:0",
                    help="pin the cluster-proxy bind address")
    sv.add_argument("--metrics-address", default="127.0.0.1:0",
                    help="pin the /metrics bind address")
    sv.add_argument("--connect-bus", default="",
                    help="HA replica mode: run the controller fleet over a "
                    "StoreReplica of this external store-bus address "
                    "(python -m karmada_tpu.bus) instead of hosting the "
                    "store; pairs with --leader-elect")
    sv.add_argument("--leader-elect", action="store_true",
                    help="Lease-CAS active-standby (every reference binary's "
                    "--leader-elect); implied by --connect-bus")
    sv.add_argument("--identity", default="",
                    help="leader-election identity (default plane-<pid>)")
    sv.add_argument("--lease-duration", type=float, default=15.0)
    sv.add_argument("--renew-deadline", type=float, default=10.0)
    sv.add_argument("--warmup-manifest", default=None,
                    help="trace-manifest path to AOT-prewarm the in-proc "
                    "scheduler from before the first settle (default: "
                    "$KARMADA_TPU_TRACE_MANIFEST; with --solver the "
                    "sidecar prewarms itself instead)")

    up = sub.add_parser("up", help="spawn the full multi-process deployment")
    up.add_argument("--members", type=int, default=2)
    # default applied after parsing: an append action with a non-empty
    # default list would APPEND user values to it (no way to drop pull1)
    up.add_argument("--pull", action="append", default=None)
    up.add_argument("--warmup-manifest", default=None,
                    help="trace-manifest path handed to the scheduling-"
                    "owning child (solver sidecar when present, else the "
                    "plane) for boot-phase AOT prewarm (default: "
                    "$KARMADA_TPU_TRACE_MANIFEST)")

    args = p.parse_args(argv)
    # chaos: arm deterministic fault injection from the environment
    # (KARMADA_TPU_FAULT_SPEC; disarmed when empty — zero overhead)
    from .utils.faultinject import arm_from_env

    arm_from_env()
    if args.command == "up" and args.pull is None:
        args.pull = ["pull1"]
    if args.command == "serve":
        if args.leader_elect and not args.connect_bus:
            # election needs the shared store: a lone plane hosting its own
            # store has nothing to elect against — failing loudly beats an
            # operator believing a single-writer plane is HA
            p.error("--leader-elect requires --connect-bus (the shared "
                    "store-bus the replicas elect over)")
        serve_plane(args)
    elif args.command == "up":
        with LocalUp(
            members=args.members, pull=tuple(args.pull),
            warmup_manifest=args.warmup_manifest,
        ) as lu:
            print(json.dumps(lu.endpoints), flush=True)
            try:
                while all(p.poll() is None for p in lu.procs.values()):
                    time.sleep(1)
            except KeyboardInterrupt:
                pass


if __name__ == "__main__":
    main()
