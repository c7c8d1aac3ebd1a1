"""Multi-process deployment e2e: the hack/local-up-karmada.sh +
hack/run-e2e.sh tier (VERDICT r3 items 4/5/7).

``LocalUp`` spawns solver sidecar, estimator server, the plane (store bus +
cluster proxy + /metrics) and a pull-mode agent as REAL OS processes; every
assertion here drives the system through network surfaces only — the bus
(gRPC), the proxy (HTTP), /metrics (HTTP), and the remote CLI as its own
subprocess. Nothing in this file touches a ControlPlane object directly.
"""

import collections
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from karmada_tpu.api import PropagationPolicy, PropagationSpec, ResourceSelector
from karmada_tpu.api.core import ObjectMeta
from karmada_tpu.bus.service import StoreReplica
from karmada_tpu.localup import LocalUp
from karmada_tpu.utils.builders import duplicated_placement, new_deployment


def wait_for(predicate, timeout=30.0, interval=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def run_cli(*args: str) -> str:
    out = subprocess.run(
        [sys.executable, "-m", "karmada_tpu.cli", *args],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, f"cli {args} failed: {out.stdout}\n{out.stderr}"
    return out.stdout


def follow(proc: subprocess.Popen) -> collections.deque:
    """Read what a child writes from here on, on a daemon thread, and keep
    the last of it. LocalUp reads a child's one pipe (stdout and stderr)
    only until the lines it waits for have come; a child that then writes
    the pipe full (64 KiB: XLA's line on loading a cached executable is
    3.6 KB) blocks in that write, in the middle of whatever it serves."""
    tail: collections.deque = collections.deque(maxlen=8)
    fd = proc.stdout.fileno()

    def pump() -> None:
        try:
            while chunk := os.read(fd, 1 << 16):
                tail.append(chunk[-1024:].decode(errors="replace"))
        except (OSError, ValueError):
            pass  # the pipe closed under us: the child is gone

    threading.Thread(target=pump, daemon=True).start()
    return tail


def state_of(lu, replica, key) -> str:
    """For the message of a wait that ran out: the binding as the replica
    holds it, each child alive or not, the last each child wrote."""
    rb = replica.store.get("ResourceBinding", key)
    lines = [f"binding {key}: " + (
        "absent" if rb is None
        else f"clusters={[(tc.name, tc.replicas) for tc in rb.spec.clusters]}")]
    for name, proc in lu.procs.items():
        lines.append(f"[{name}] rc={proc.poll()} ...{''.join(lu.tails[name])[-600:]}")
    return "\n".join(lines)


@pytest.fixture(scope="module")
def deployment():
    with LocalUp(members=2, pull=("pull1",), lease_grace=3.0) as lu:
        lu.tails = {name: follow(proc) for name, proc in lu.procs.items()}
        replica = StoreReplica(f"127.0.0.1:{lu.endpoints['bus']}")
        replica.start()
        assert replica.wait_synced(10)
        yield lu, replica
        replica.close()


class TestMultiProcessQuickstart:
    def test_quickstart_through_network_surfaces(self, deployment):
        lu, r = deployment
        # platform policy: control-plane components run CPU jax; the
        # scraped backend confirms the solver honored it (the TPU-owning
        # variant is tests/test_tpu_solver_localup.py, opt-in)
        assert lu.solver_backend == "cpu"
        # all three clusters visible over the bus
        assert wait_for(
            lambda: {c.name for c in r.store.list("Cluster")}
            >= {"member1", "member2", "pull1"}
        )
        # quickstart: apply template + policy THROUGH the bus
        r.apply(new_deployment("nginx", replicas=2))
        r.apply(
            PropagationPolicy(
                meta=ObjectMeta(name="nginx-policy", namespace="default"),
                spec=PropagationSpec(
                    resource_selectors=[
                        ResourceSelector(api_version="apps/v1", kind="Deployment")
                    ],
                    placement=duplicated_placement(),
                ),
            )
        )

        def scheduled_everywhere():
            rb = r.store.get("ResourceBinding", "default/nginx-deployment")
            if rb is None:
                return False
            placed = {tc.name for tc in rb.spec.clusters}
            return placed >= {"member1", "member2", "pull1"}

        assert wait_for(scheduled_everywhere), "binding never spanned all clusters"

        # the out-of-process agent applied the Work and reflected status
        def pull_work_applied():
            w = r.store.get("Work", "karmada-es-pull1/default.nginx-deployment")
            return w is not None and any(
                c.type == "Applied" and c.status for c in w.status.conditions
            )

        assert wait_for(pull_work_applied), "pull agent never applied the Work"

        # aggregated status reaches the binding for the pull member
        def aggregated():
            rb = r.store.get("ResourceBinding", "default/nginx-deployment")
            return any(
                i.cluster_name == "pull1" and i.applied
                for i in rb.status.aggregated_status
            )

        assert wait_for(aggregated), "no aggregated status from the pull member"

    def test_remote_cli_reads_and_writes(self, deployment):
        lu, r = deployment
        bus = f"127.0.0.1:{lu.endpoints['bus']}"
        proxy = f"127.0.0.1:{lu.endpoints['proxy']}"

        # get (fleet scope, from the karmada tier)
        out = run_cli(
            "--bus", bus, "--proxy", proxy,
            "get", "apps/v1/Deployment", "--namespace", "default",
            "--name", "nginx",
        )
        obj = json.loads(out)
        assert obj["meta"]["name"] == "nginx"

        # cluster-scoped get rides the HTTP proxy passthrough (the member
        # object as applied by the plane's execution controller)
        def member_get():
            try:
                out = run_cli(
                    "--bus", bus, "--proxy", proxy,
                    "get", "apps/v1/Deployment", "--namespace", "default",
                    "--name", "nginx", "--cluster", "member1",
                )
                return json.loads(out)["meta"]["name"] == "nginx"
            except AssertionError:
                return False

        assert wait_for(member_get), "cluster-scoped remote get never served"

        # describe aggregates binding placements
        out = run_cli(
            "--bus", bus, "describe", "apps/v1/Deployment", "default", "nginx"
        )
        assert "placements:" in out and "pull1" in out

        # cordon/uncordon round-trip THROUGH the bus (write path + admission)
        run_cli("--bus", bus, "cordon", "member2")
        assert wait_for(
            lambda: any(
                t.key == "node.karmada.io/unschedulable"
                for t in r.store.get("Cluster", "member2").spec.taints
            )
        )
        run_cli("--bus", bus, "uncordon", "member2")
        assert wait_for(
            lambda: not any(
                t.key == "node.karmada.io/unschedulable"
                for t in r.store.get("Cluster", "member2").spec.taints
            )
        )

    def test_remote_cli_generic_verbs(self, deployment, tmp_path):
        """VERDICT r3 item 8: the kubectl-style write surface over the bus
        (pkg/karmadactl/karmadactl.go:98-178 — apply/patch/label/annotate/
        delete/api-resources), with admission enforced SERVER-SIDE in the
        plane process."""
        lu, r = deployment
        bus = f"127.0.0.1:{lu.endpoints['bus']}"

        # apply: a Deployment template + a policy, one manifest file
        manifest = tmp_path / "app.json"
        manifest.write_text(json.dumps([
            {
                "apiVersion": "apps/v1", "kind": "Deployment",
                "metadata": {"name": "verbs-app", "namespace": "default"},
                "spec": {"replicas": 4},
            },
            {
                "kind": "PropagationPolicy",
                "metadata": {"name": "verbs-pp", "namespace": "default"},
                "spec": {
                    "resource_selectors": [
                        {"api_version": "apps/v1", "kind": "Deployment",
                         "name": "verbs-app"}
                    ],
                    "placement": {
                        "replica_scheduling": {
                            "replica_scheduling_type": "Divided",
                            "replica_division_preference": "Weighted",
                        }
                    },
                },
            },
        ]))
        out = run_cli("--bus", bus, "apply", "-f", str(manifest))
        assert "Resource/default/verbs-app" in out
        assert "PropagationPolicy/default/verbs-pp" in out

        def divided(total):
            def check():
                rb = r.store.get(
                    "ResourceBinding", "default/verbs-app-deployment"
                )
                return rb is not None and sum(
                    tc.replicas for tc in rb.spec.clusters
                ) == total
            return check

        # the plane's first Divided solve goes to the solver sidecar, which
        # compiles a kernel shape for it. In full runs of PR 9, 14, 36 and
        # 37 this wait ran out (at 30 s, then at 90 s, in a test that takes
        # 21 s alone) and nobody could say where the 90 s went. The limit
        # now covers the slowest path there is (the solve RPC's own 120 s
        # deadline, then the plane's in-process fallback), no child can
        # block on its pipe (``follow``), and a wait that runs out says
        # what the plane and the sidecar were doing
        assert wait_for(divided(4), timeout=240.0), (
            "applied workload never scheduled\n" + state_of(
                lu, r, "default/verbs-app-deployment"))

        # patch: bump replicas through the bus; the binding re-divides
        out = run_cli(
            "--bus", bus, "patch", "apps/v1/Deployment", "default",
            "verbs-app", "-p", json.dumps({"spec": {"replicas": 9}}),
        )
        assert json.loads(out)["spec"]["replicas"] == 9
        assert wait_for(divided(9), timeout=120.0), (
            "patched replica count never re-divided\n" + state_of(
                lu, r, "default/verbs-app-deployment"))

        # label + annotate round-trip
        out = run_cli(
            "--bus", bus, "label", "apps/v1/Deployment", "default",
            "verbs-app", "tier=web", "junk-",
        )
        assert json.loads(out)["meta"]["labels"]["tier"] == "web"
        out = run_cli(
            "--bus", bus, "annotate", "apps/v1/Deployment", "default",
            "verbs-app", "owner=cli-e2e",
        )
        assert json.loads(out)["meta"]["annotations"]["owner"] == "cli-e2e"

        # admission observed: an invalid policy is REJECTED by the plane's
        # chain, server-side, through the same wire path
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({
            "kind": "PropagationPolicy",
            "metadata": {"name": "bad-pp", "namespace": "default"},
            "spec": {"resource_selectors": []},
        }))
        proc = subprocess.run(
            [sys.executable, "-m", "karmada_tpu.cli", "--bus", bus,
             "apply", "-f", str(bad)],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1
        assert "resourceSelectors" in proc.stdout
        assert r.store.get("PropagationPolicy", "default/bad-pp") is None

        # api-resources discovery
        out = run_cli("--bus", bus, "api-resources")
        kinds = {e["kind"] for e in json.loads(out)}
        assert {"PropagationPolicy", "Cluster", "apps/v1/Deployment"} <= kinds

        # delete: template gone; binding cleaned up by the detector
        out = run_cli(
            "--bus", bus, "delete", "apps/v1/Deployment", "default",
            "verbs-app",
        )
        assert "deleted" in out
        assert wait_for(
            lambda: r.store.get("Resource", "default/verbs-app") is None
        )

    def test_cluster_proxy_passthrough_serves_member_state(self, deployment):
        lu, r = deployment
        # the deployment propagated to member1 inside the plane process; the
        # HTTP proxy passthrough reads it back out (impersonation + REST)
        req = urllib.request.Request(
            f"http://127.0.0.1:{lu.endpoints['proxy']}"
            "/apis/cluster.karmada.io/v1alpha1/clusters/member1/proxy"
            "/apis/apps/v1/namespaces/default/deployments/nginx",
            headers={"Authorization": "Bearer admin-token"},
        )

        def proxied():
            try:
                with urllib.request.urlopen(req, timeout=5) as resp:
                    body = json.loads(resp.read())
                return body["metadata"]["name"] == "nginx"
            except Exception:
                return False

        assert wait_for(proxied), "proxy passthrough never served the object"

    def test_metrics_endpoint_serves_scheduler_metrics(self, deployment):
        lu, _r = deployment
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{lu.endpoints['metrics']}/metrics", timeout=5
        ).read().decode()
        assert "karmada_scheduler_schedule_attempts_total" in body
        # scheduling happened in the quickstart: at least one sample line
        assert any(
            line and not line.startswith("#") for line in body.splitlines()
        ), body
        health = urllib.request.urlopen(
            f"http://127.0.0.1:{lu.endpoints['metrics']}/healthz", timeout=5
        ).read()
        assert health == b"ok\n"

    def test_agent_process_death_fails_workload_over(self, deployment):
        """Runs LAST in the module: kills the pull agent process and expects
        the lease to go stale (grace shortened to 3s), the cluster to
        degrade, and the binding to rehome onto surviving members."""
        lu, r = deployment
        lu.kill("agent-pull1")

        def failed_over():
            rb = r.store.get("ResourceBinding", "default/nginx-deployment")
            placed = {tc.name for tc in rb.spec.clusters}
            return "pull1" not in placed and placed >= {"member1", "member2"}

        assert wait_for(failed_over, timeout=45.0), (
            "binding never left the dead pull cluster"
        )
        cluster = r.store.get("Cluster", "pull1")
        ready = next(c for c in cluster.status.conditions if c.type == "Ready")
        assert not ready.status
