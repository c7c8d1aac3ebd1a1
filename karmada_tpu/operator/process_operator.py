"""Process-deployment operator: the Karmada CR installs REAL processes.

Ref: operator/pkg/tasks/init — the reference operator's core job is
standing up certs, etcd, the apiserver and every component as actual
workloads, then reconciling spec drift against the running deployment.
``KarmadaOperator`` (karmada_operator.py) keeps the task-graph/upgrade
semantics in-process; THIS operator runs the same workflow engine but its
tasks manage OS processes and PKI:

  validate -> certs (openssl CA + server cert) -> admission webhook (TLS
  process) -> solver sidecar -> estimator server -> control plane (bus +
  proxy + /metrics, wired to every sidecar) -> pull agents -> wait-ready
  (healthz + bus sync probes)

Upgrade reconciles diff the applied spec: component enable/disable
restarts the affected processes; version skew is validated before any
restart; pull-member changes start/stop agent processes. Deinit tears the
processes down in reverse order and removes the instance PKI.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Optional

from ..api.core import Condition, set_condition
from .karmada_operator import (
    Karmada,
    KarmadaSpec,
    _spec_copy,
    validate_version_skew,
)
from .workflow import Job, Task


@dataclass
class ProcessInstance:
    """One installed deployment: endpoints + child processes + PKI."""

    name: str
    pki_dir: str = ""
    procs: dict[str, subprocess.Popen] = field(default_factory=dict)
    endpoints: dict[str, object] = field(default_factory=dict)
    solver_backend: str = ""  # scraped when the solver owns an accelerator

    def alive(self, component: str) -> bool:
        proc = self.procs.get(component)
        return proc is not None and proc.poll() is None


from ..localup import (
    scrape_line as _scrape,
    scrape_solver_backend,
    spawn_child as _spawn,
)


@dataclass
class ComponentHealth:
    """Per-component supervision state (the CrashLoopBackOff analogue:
    Kubernetes' kubelet applies exponential backoff to a container that
    keeps dying; the reference operator inherits that for free from the
    Deployments it renders — this build supplies it directly)."""

    restarts: int = 0  # lifetime restart count (surfaced on the CR)
    recent: list = field(default_factory=list)  # restart times in window
    backoff: float = 0.0  # current backoff seconds (0 = none)
    backoff_until: float = 0.0  # monotonic deadline; dead waits until then
    last_restart: float = 0.0

    def reset(self) -> None:
        self.recent.clear()
        self.backoff = 0.0
        self.backoff_until = 0.0


def _stop(proc: Optional[subprocess.Popen], grace: float = 5.0) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=grace)


class ProcessKarmadaOperator:
    """Reconciles Karmada CRs into multi-process deployments."""

    def __init__(
        self,
        checkpoint_interval: float = 15.0,
        backoff_initial: float = 1.0,
        backoff_max: float = 30.0,
        storm_window: float = 30.0,
        storm_cap: int = 5,
    ) -> None:
        self.instances: dict[str, ProcessInstance] = {}
        self._applied_specs: dict[str, KarmadaSpec] = {}
        self.checkpoint_interval = checkpoint_interval
        # supervision policy: first death restarts immediately; repeat
        # deaths wait an exponentially growing backoff (doubling to
        # backoff_max); more than storm_cap restarts inside storm_window
        # is a CRASH LOOP — restarts continue at max backoff and the CR
        # reports ComponentsHealthy=False/CrashLoopBackOff
        self.backoff_initial = backoff_initial
        self.backoff_max = backoff_max
        self.storm_window = storm_window
        self.storm_cap = storm_cap
        self._health: dict[tuple[str, str], ComponentHealth] = {}
        import threading

        self._lock = threading.RLock()  # reconcile vs watchdog sweeps

    # -- public ------------------------------------------------------------

    def reconcile(self, karmada: Karmada) -> ProcessInstance:
        with self._lock:
            return self._reconcile_locked(karmada)

    def _reconcile_locked(self, karmada: Karmada) -> ProcessInstance:
        name = karmada.meta.name
        fresh = name not in self.instances
        job = (
            self._init_job(karmada) if fresh else self._upgrade_job(karmada)
        )
        karmada.status.failed_task = ""
        try:
            job.run()
            set_condition(
                karmada.status.conditions,
                Condition(type="Ready", status=True, reason="Completed"),
            )
            karmada.status.installed_version = karmada.spec.version
            karmada.status.observed_generation = karmada.meta.generation
            self._applied_specs[name] = _spec_copy(karmada.spec)
        except Exception as e:
            karmada.status.failed_task = getattr(e, "task_name", "")
            set_condition(
                karmada.status.conditions,
                Condition(type="Ready", status=False, reason="TaskFailed",
                          message=str(e)),
            )
            if fresh:
                inst = self.instances.pop(name, None)
                if inst is not None:
                    self._teardown(inst)
            raise
        finally:
            karmada.status.completed_tasks = list(job.completed)
        return self.instances[name]

    def supervise(self, karmada: Karmada) -> list[str]:
        """One supervision sweep: restart any dead component of an
        installed instance at its PINNED endpoint, under the crash-loop
        policy (exponential backoff per component, restart-storm cap
        surfaced on the CR). The plane restarts from its latest periodic
        checkpoint; gRPC clients (RemoteSolver, estimator connections,
        StoreReplica agents) reconnect to the pinned ports on their own —
        the solver's snapshot-version fencing re-syncs cluster state on
        the first post-restart schedule. Returns the component names
        restarted this sweep (a component inside its backoff window stays
        down and is NOT in the list). ``Supervisor`` wraps this in a
        watchdog thread."""
        with self._lock:
            return self._supervise_locked(karmada)

    def _supervise_locked(self, karmada: Karmada) -> list[str]:
        name = karmada.meta.name
        inst = self.instances.get(name)
        if inst is None:
            return []
        now = time.monotonic()
        data = {"karmada": karmada}
        restarted: list[str] = []
        starters = {
            "webhook": self._start_webhook,
            "solver": self._start_solver,
            "estimator": self._start_estimator,
            "plane": self._start_plane,
        }
        for comp, proc in list(inst.procs.items()):
            h = self._health.setdefault((name, comp), ComponentHealth())
            if proc.poll() is None:
                # alive past the storm window: forgive the history so a
                # one-off crash next month starts from a fresh backoff
                if h.backoff and now - h.last_restart > self.storm_window:
                    h.reset()
                continue
            if now < h.backoff_until:
                continue  # backing off: stays down this sweep
            try:
                if comp.startswith("agent-"):
                    self._spawn_agent(inst, comp[len("agent-"):])
                else:
                    starters[comp](data)
                started = True
            except Exception:
                # a FAILED restart attempt (child died during startup,
                # scrape timeout) must still advance the backoff — or the
                # watchdog would hot-loop respawns with no cap at all
                started = False
            # the backoff clock starts when the restart attempt COMPLETES:
            # child startup (imports, port scrape) can take many seconds,
            # and a deadline anchored at sweep start would be expired
            t_done = time.monotonic()
            h.restarts += 1
            h.last_restart = t_done
            h.recent = [
                t for t in h.recent if t_done - t <= self.storm_window
            ] + [t_done]
            h.backoff = min(
                self.backoff_max,
                h.backoff * 2 if h.backoff else self.backoff_initial,
            )
            h.backoff_until = t_done + h.backoff
            if started:
                restarted.append(comp)
        self._surface_health(karmada, now)
        if restarted:
            self._wait_ready(data)
        return restarted

    def _surface_health(self, karmada: Karmada, now: float) -> None:
        """Crash-loop status on the Karmada CR (the reference surfaces
        component failures as Karmada CR conditions via its controller;
        operator/pkg/controller/karmada condition plumbing)."""
        name = karmada.meta.name
        inst = self.instances.get(name)
        karmada.status.component_restarts = {
            comp: h.restarts
            for (n, comp), h in self._health.items()
            if n == name and h.restarts
        }
        # crash loop = storm_cap exceeded inside the window OR the backoff
        # has been driven to its max (with doubling backoff the window can
        # physically hold only ~storm_cap restarts, so max-backoff is the
        # steady-state signature of a perpetually dying component)
        looping = sorted(
            comp
            for (n, comp), h in self._health.items()
            if n == name
            and (
                len([t for t in h.recent if now - t <= self.storm_window])
                > self.storm_cap
                or (h.backoff >= self.backoff_max and h.recent)
            )
        )
        dead = sorted(
            comp
            for comp in (inst.procs if inst else {})
            if not inst.alive(comp)
        )
        if looping:
            msgs = []
            for comp in looping:
                h = self._health[(name, comp)]
                msgs.append(
                    f"{comp}: {h.restarts} restarts "
                    f"({len(h.recent)} in {self.storm_window:.0f}s), "
                    f"backoff {h.backoff:.1f}s"
                )
            set_condition(
                karmada.status.conditions,
                Condition(
                    type="ComponentsHealthy", status=False,
                    reason="CrashLoopBackOff", message="; ".join(msgs),
                ),
            )
        elif dead:
            # down but not yet looping: waiting out a backoff window
            set_condition(
                karmada.status.conditions,
                Condition(
                    type="ComponentsHealthy", status=False,
                    reason="BackOff", message=", ".join(dead) + " down",
                ),
            )
        else:
            set_condition(
                karmada.status.conditions,
                Condition(
                    type="ComponentsHealthy", status=True, reason="AllAlive"
                ),
            )

    def deinit(self, karmada: Karmada) -> None:
        inst = self.instances.pop(karmada.meta.name, None)
        self._applied_specs.pop(karmada.meta.name, None)
        if inst is not None:
            self._teardown(inst)
        set_condition(
            karmada.status.conditions,
            Condition(type="Ready", status=False, reason="Removed"),
        )

    def _teardown(self, inst: ProcessInstance) -> None:
        # reverse start order: agents, plane, sidecars, webhook
        for comp in reversed(list(inst.procs)):
            _stop(inst.procs[comp])
        if inst.pki_dir and os.path.isdir(inst.pki_dir):
            shutil.rmtree(inst.pki_dir, ignore_errors=True)

    # -- init pipeline -----------------------------------------------------

    def _init_job(self, karmada: Karmada) -> Job:
        karmada_spec = karmada.spec
        return Job(
            tasks=[
                Task(name="validate", run=self._validate),
                Task(name="certs", run=self._certs),
                Task(
                    name="webhook", run=self._start_webhook,
                    skip=lambda d: not karmada_spec.components.webhook.enabled,
                ),
                Task(name="solver", run=self._start_solver),
                Task(
                    name="estimator", run=self._start_estimator,
                    skip=lambda d: not karmada_spec.components.estimators.enabled,
                ),
                Task(name="control-plane", run=self._start_plane),
                Task(name="agents", run=self._start_agents),
                Task(name="wait-ready", run=self._wait_ready),
            ],
            data={"karmada": karmada},
        )

    def _instance(self, data: dict) -> ProcessInstance:
        karmada = data["karmada"]
        inst = self.instances.get(karmada.meta.name)
        if inst is None:
            inst = ProcessInstance(name=karmada.meta.name)
            self.instances[karmada.meta.name] = inst
        return inst

    def _validate(self, data: dict) -> None:
        karmada = data["karmada"]
        validate_version_skew(karmada.spec.version, karmada.spec.components)
        self._instance(data)

    def _certs(self, data: dict) -> None:
        """operator/pkg/tasks/init cert task: a real self-signed PKI for
        the instance's TLS surfaces (admission webhook)."""
        inst = self._instance(data)
        inst.pki_dir = tempfile.mkdtemp(prefix=f"karmada-pki-{inst.name}-")
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", os.path.join(inst.pki_dir, "webhook.key"),
             "-out", os.path.join(inst.pki_dir, "webhook.crt"),
             "-days", "3650", "-subj", "/CN=localhost",
             "-addext", "subjectAltName=IP:127.0.0.1,DNS:localhost"],
            check=True, capture_output=True,
        )

    def _start_webhook(self, data: dict) -> None:
        inst = self._instance(data)
        # pinned on restart: the live plane's RemoteAdmission keeps dialing
        # the URL it was constructed with
        prev = str(inst.endpoints.get("webhook", ""))
        port = prev.rsplit(":", 1)[-1].split("/")[0] if prev else "0"
        proc = _spawn(
            [sys.executable, "-m", "karmada_tpu.webhook.server",
             "--address", f"127.0.0.1:{port}",
             "--certfile", os.path.join(inst.pki_dir, "webhook.crt"),
             "--keyfile", os.path.join(inst.pki_dir, "webhook.key")]
        )
        inst.procs["webhook"] = proc
        port = _scrape(proc, r"listening on port (\d+)")
        inst.endpoints["webhook"] = f"https://127.0.0.1:{port}/admit"

    def _start_solver(self, data: dict) -> None:
        inst = self._instance(data)
        karmada = data["karmada"]
        platform = karmada.spec.components.solver.platform or "cpu"
        port = inst.endpoints.get("solver", 0)  # pinned on restart
        cmd = [sys.executable, "-m", "karmada_tpu.solver",
               "--address", f"127.0.0.1:{port}"]
        if platform != "cpu":
            cmd.append("--report-backend")
        proc = _spawn(cmd, platform=platform)
        inst.procs["solver"] = proc
        inst.endpoints["solver"] = int(_scrape(proc, r"port (\d+)"))
        if platform != "cpu":
            # the sidecar must own the platform the spec asked for: a
            # solver that came up on another backend would fake the
            # deployment shape, so a mismatch raises
            inst.solver_backend = scrape_solver_backend(proc, platform)

    def _start_estimator(self, data: dict) -> None:
        inst = self._instance(data)
        port = inst.endpoints.get("estimator", 0)  # pinned on restart
        proc = _spawn(
            [sys.executable, "-m", "karmada_tpu.estimator",
             "--cluster", "member1", "--address", f"127.0.0.1:{port}"]
        )
        inst.procs["estimator"] = proc
        inst.endpoints["estimator"] = int(_scrape(proc, r"port (\d+)"))

    def _plane_cmd(self, data: dict) -> list[str]:
        inst = self._instance(data)
        karmada = data["karmada"]
        spec = karmada.spec
        cmd = [
            sys.executable, "-m", "karmada_tpu.localup", "serve",
            "--members", str(max(1, len(spec.member_clusters) or 2)),
            "--state-file", os.path.join(inst.pki_dir, "store.ckpt"),
            "--checkpoint-interval", str(self.checkpoint_interval),
        ]
        # pinned surfaces on restart: agents / CLIs / supervision probes
        # keep their targets across plane replacements
        if "bus" in inst.endpoints:
            cmd += ["--bus-address", f"127.0.0.1:{inst.endpoints['bus']}"]
        if "proxy" in inst.endpoints:
            cmd += ["--proxy-address", f"127.0.0.1:{inst.endpoints['proxy']}"]
        if "metrics" in inst.endpoints:
            cmd += ["--metrics-address", f"127.0.0.1:{inst.endpoints['metrics']}"]
        for name in spec.pull_members:
            cmd += ["--pull", name]
        if "solver" in inst.endpoints:
            cmd += ["--solver", f"127.0.0.1:{inst.endpoints['solver']}"]
        if "estimator" in inst.endpoints:
            cmd += [
                "--estimator", f"member1=127.0.0.1:{inst.endpoints['estimator']}"
            ]
        if "webhook" in inst.endpoints:
            cmd += [
                "--admission", inst.endpoints["webhook"],
                "--admission-ca", os.path.join(inst.pki_dir, "webhook.crt"),
            ]
        if spec.components.descheduler.enabled:
            cmd += ["--descheduler"]
        gates = dict(spec.feature_gates)
        if gates:
            cmd += [
                "--feature-gates",
                ",".join(f"{k}={str(v).lower()}" for k, v in gates.items()),
            ]
        return cmd

    def _start_plane(self, data: dict) -> None:
        inst = self._instance(data)
        proc = _spawn(self._plane_cmd(data))
        inst.procs["plane"] = proc
        # anchor on a JSON object (json.dumps always opens with `{"`):
        # the child's stderr is merged into the scraped stream, and a
        # stray log line containing braces (grpc error reprs carry
        # `{grpc_status:...}`) must not masquerade as the endpoints line
        line = _scrape(proc, r"(\{\".*\})")
        info = json.loads(line)
        inst.endpoints.update(
            bus=info["bus"], proxy=info["proxy"], metrics=info["metrics"],
            clusters=info["clusters"],
        )

    def _spawn_agent(self, inst: ProcessInstance, name: str) -> None:
        inst.procs[f"agent-{name}"] = _spawn(
            [sys.executable, "-m", "karmada_tpu.bus.agent",
             "--target", f"127.0.0.1:{inst.endpoints['bus']}",
             "--cluster", name]
        )

    def _start_agents(self, data: dict) -> None:
        inst = self._instance(data)
        karmada = data["karmada"]
        for name in karmada.spec.pull_members:
            self._spawn_agent(inst, name)

    def _wait_ready(self, data: dict) -> None:
        inst = self._instance(data)
        deadline = time.time() + 30
        url = f"http://127.0.0.1:{inst.endpoints['metrics']}/healthz"
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.read() == b"ok\n":
                        return
            except Exception:
                time.sleep(0.2)
        raise RuntimeError("control plane never became healthy")

    # -- upgrade reconcile -------------------------------------------------

    def _upgrade_job(self, karmada: Karmada) -> Job:
        prev = self._applied_specs[karmada.meta.name]
        spec = karmada.spec
        tasks = [Task(name="validate", run=self._validate)]
        # ANY field consumed by _plane_cmd (or by the sidecars it points
        # at) that drifted forces a plane restart — a partial diff here
        # would silently diverge the deployment from the CR while
        # reporting Ready
        plane_restart = (
            spec.components.descheduler.enabled
            != prev.components.descheduler.enabled
            or spec.feature_gates != prev.feature_gates
            or spec.pull_members != prev.pull_members
            or spec.member_clusters != prev.member_clusters
            or spec.version != prev.version
        )
        if (
            spec.components.estimators.enabled
            != prev.components.estimators.enabled
        ):
            tasks.append(Task(name="estimator", run=self._toggle_estimator))
            plane_restart = True
        if (
            spec.components.webhook.enabled
            != prev.components.webhook.enabled
        ):
            tasks.append(Task(name="webhook", run=self._toggle_webhook))
            plane_restart = True
        if plane_restart:
            tasks.append(Task(name="control-plane", run=self._restart_plane))
            tasks.append(Task(name="agents", run=self._restart_agents))
        tasks.append(Task(name="wait-ready", run=self._wait_ready))
        return Job(tasks=tasks, data={"karmada": karmada})

    def _toggle_estimator(self, data: dict) -> None:
        inst = self._instance(data)
        karmada = data["karmada"]
        if karmada.spec.components.estimators.enabled:
            if not inst.alive("estimator"):
                self._start_estimator(data)
        else:
            _stop(inst.procs.pop("estimator", None))
            inst.endpoints.pop("estimator", None)

    def _toggle_webhook(self, data: dict) -> None:
        inst = self._instance(data)
        karmada = data["karmada"]
        if karmada.spec.components.webhook.enabled:
            if not inst.alive("webhook"):
                self._start_webhook(data)
        else:
            _stop(inst.procs.pop("webhook", None))
            inst.endpoints.pop("webhook", None)

    def _restart_plane(self, data: dict) -> None:
        inst = self._instance(data)
        _stop(inst.procs.pop("plane", None))
        self._start_plane(data)

    def _restart_agents(self, data: dict) -> None:
        inst = self._instance(data)
        karmada = data["karmada"]
        want = set(karmada.spec.pull_members)
        for comp in [c for c in inst.procs if c.startswith("agent-")]:
            _stop(inst.procs.pop(comp))
        for name in want:
            self._spawn_agent(inst, name)


class Supervisor:
    """Watchdog thread around ``ProcessKarmadaOperator.supervise``: the
    always-on Deployment-controller loop the reference gets from
    Kubernetes itself. Polls component liveness every ``interval``
    seconds, restarts dead components under the operator's backoff /
    crash-loop policy, and keeps the Karmada CR's ComponentsHealthy
    condition current. One Supervisor per CR; sweeps and reconciles share
    the operator's lock."""

    def __init__(
        self,
        operator: ProcessKarmadaOperator,
        karmada: Karmada,
        interval: float = 0.5,
    ) -> None:
        import threading

        self.operator = operator
        self.karmada = karmada
        self.interval = interval
        self.restarted_total: list[str] = []  # log of restart events
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Supervisor":
        import threading

        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.restarted_total.extend(
                    self.operator.supervise(self.karmada)
                )
            except Exception:  # noqa: BLE001 — the watchdog must survive
                # a failed restart attempt (it retries next sweep; the
                # component's backoff keeps growing)
                pass
            self._stop.wait(self.interval)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
