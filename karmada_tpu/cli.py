"""karmadactl-style operations (ref: pkg/karmadactl/karmadactl.go:98-178).

The reference CLI talks to a remote control plane; here every command is a
function over a ControlPlane handle (the in-proc apiserver seam), so the same
operations serve tests, the demo driver, and a future remote transport:

- lifecycle: init (local_up), join / unjoin (push), register / unregister
  (pull), addons
- ops: get / describe / top across clusters (via the search proxy +
  metrics adapter)
- migration: promote (import a member resource as template + policy)
- maintenance: cordon / uncordon, taint
- interpret: dry-run interpreter operations against a template
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence

from typing import TYPE_CHECKING

from .api.cluster import NO_EXECUTE, NO_SCHEDULE, PULL, Cluster, Taint
from .api.core import ObjectMeta
from .api.policy import (
    ClusterAffinity,
    Placement,
    PropagationPolicy,
    PropagationSpec,
    ResourceSelector,
)
from .utils.builders import new_cluster

if TYPE_CHECKING:  # runtime imports are DEFERRED: controlplane/search/
    # member all reach the estimator (and therefore jax) at import time,
    # and this is an entry module — the GL005 cold-start contract. The
    # lint verb additionally depends on it: the IR/dep tiers must set
    # XLA_FLAGS before this process's FIRST jax import or the sharded
    # spec variants cannot materialize their >=2-device mesh.
    from .controlplane import ControlPlane
    from .search import ProxyRequest
    from .utils.member import MemberCluster


def _proxy_request(**kw) -> "ProxyRequest":
    from .search import ProxyRequest

    return ProxyRequest(**kw)

CORDON_TAINT_KEY = "node.karmada.io/unschedulable"  # cordon analogue


# --------------------------------------------------------------------------
# remote backend: administer a plane this process did NOT construct
# --------------------------------------------------------------------------


def _plural_of() -> dict[str, tuple[str, str]]:
    """gvk -> (REST path prefix, plural), derived by inverting the proxy
    server's route table so the two sides can never drift apart."""
    from .search.proxyserver import _PLURALS

    out = {}
    for plural, gvk in _PLURALS.items():
        group_version = gvk.rsplit("/", 1)[0]
        prefix = "api/v1" if group_version == "v1" else f"apis/{group_version}"
        out[gvk] = (prefix, plural)
    return out


class _RemoteProxyChain:
    """The ``Proxy.connect`` surface over the wire: fleet-wide reads serve
    from the bus mirror (the karmada tier), cluster-scoped requests ride
    the HTTP cluster-proxy passthrough (the cluster tier).
    Ref: pkg/karmadactl talks to the aggregated apiserver the same way."""

    def __init__(self, store, proxy_target: str, token: str):
        self.store = store
        self.proxy_target = proxy_target
        self.token = token

    def _http(self, path: str, timeout: float = 10.0):
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            f"http://{self.proxy_target}{path}",
            headers={"Authorization": f"Bearer {self.token}"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    def connect(self, req: "ProxyRequest"):
        from .interpreter.webhook import resource_from_dict
        from .search.proxy import ProxyResponse

        if req.cluster is None:
            # fleet scope: mirror of the control-plane store (karmada tier)
            if req.verb == "get":
                key = (
                    f"{req.namespace}/{req.name}" if req.namespace else req.name
                )
                obj = self.store.get("Resource", key)
                if obj is not None and f"{obj.api_version}/{obj.kind}" == req.gvk:
                    return ProxyResponse(served_by="karmada", obj=obj)
                return ProxyResponse(served_by="karmada", error="not found")
            if req.verb == "list":
                items = [
                    ("karmada", o)
                    for o in self.store.list("Resource", req.namespace or None)
                    if f"{o.api_version}/{o.kind}" == req.gvk
                    and all(
                        o.meta.labels.get(k) == v for k, v in req.labels.items()
                    )
                ]
                return ProxyResponse(served_by="karmada", items=items)
            return ProxyResponse(
                served_by="karmada", error=f"verb {req.verb} requires cluster routing"
            )
        base = (
            "/apis/cluster.karmada.io/v1alpha1/clusters/"
            f"{req.cluster}/proxy"
        )
        if req.verb == "logs":
            tail = req.options.get("tail")
            qs = f"?tailLines={tail}" if tail else ""
            status, body = self._http(
                f"{base}/api/v1/namespaces/{req.namespace}/pods/"
                f"{req.name}/log{qs}"
            )
            if status != 200:
                return ProxyResponse(served_by="cluster", error=body)
            return ProxyResponse(
                served_by="cluster", data=body.splitlines()
            )
        if req.verb in ("exec", "attach"):
            # the streaming exec/attach subresource (chunked through the
            # proxy; a SubprocessExecRuntime member pipes a REAL process)
            import urllib.parse as _q

            cmd = (req.options or {}).get("command") or []
            qs = "&".join(f"command={_q.quote(str(c))}" for c in cmd)
            sub = "exec" if req.verb == "exec" else "attach"
            # a silent-but-running command sends no chunks: outlive the
            # member runtime's own 30s process bound with headroom
            status, body = self._http(
                f"{base}/api/v1/namespaces/{req.namespace}/pods/"
                f"{req.name}/{sub}" + (f"?{qs}" if qs else ""),
                timeout=float((req.options or {}).get("timeout", 60.0)),
            )
            if status != 200:
                return ProxyResponse(served_by="cluster", error=body)
            from .utils.member import split_exec_trailer

            lines, rc = split_exec_trailer(body.splitlines())
            return ProxyResponse(
                served_by="cluster",
                data={"stdout": "\n".join(lines), "rc": rc,
                      "lines": lines},
            )
        mapped = _plural_of().get(req.gvk)
        if mapped is None:
            return ProxyResponse(
                served_by="cluster", error=f"gvk {req.gvk} not proxied"
            )
        prefix, plural = mapped
        path = f"{base}/{prefix}/namespaces/{req.namespace}/{plural}"
        if req.verb == "get":
            status, body = self._http(f"{path}/{req.name}")
            if status != 200:
                return ProxyResponse(served_by="cluster", error=body)
            return ProxyResponse(
                served_by="cluster", obj=resource_from_dict(json.loads(body))
            )
        if req.verb == "list":
            qs = ""
            if req.labels:
                # forward the selector so a member API that honors it
                # prunes the list server-side (the client-side filter
                # below stays the guarantee either way)
                import urllib.parse as _q

                sel = ",".join(f"{k}={v}" for k, v in req.labels.items())
                qs = f"?labelSelector={_q.quote(sel)}"
            status, body = self._http(path + qs)
            if status != 200:
                return ProxyResponse(served_by="cluster", error=body)
            items = [
                (req.cluster, resource_from_dict(i))
                for i in json.loads(body).get("items", [])
            ]
            if req.labels:
                # the member API behind the passthrough may or may not
                # honor a labelSelector param; filtering here guarantees
                # the selector semantics either way (fleet-scope and
                # in-proc proxy branches already filter)
                items = [
                    (c, o)
                    for c, o in items
                    if all(
                        o.meta.labels.get(k) == v
                        for k, v in req.labels.items()
                    )
                ]
            return ProxyResponse(served_by="cluster", items=items)
        return ProxyResponse(
            served_by="cluster", error=f"verb {req.verb} not proxied"
        )


class RemotePlane:
    """A ControlPlane-shaped handle over the NETWORK surfaces only: state
    via the store bus (StoreReplica mirror + write-through), member access
    via the cluster-proxy HTTP server. Every ``cmd_*`` that touches only
    ``cp.store`` / ``cp.proxy`` works unchanged against it — the CLI can
    administer a plane it did not construct (VERDICT r3 item 5; ref:
    pkg/karmadactl/karmadactl.go:98-178)."""

    def __init__(
        self,
        bus_target: str,
        proxy_target: str = "",
        *,
        token: str = "admin-token",
        sync_timeout: float = 10.0,
    ):
        from .bus.agent import ReplicaStoreFacade
        from .bus.service import StoreReplica

        self._replica = StoreReplica(bus_target)
        self._replica.start()
        if not self._replica.wait_synced(sync_timeout):
            self._replica.close()
            raise RuntimeError(f"bus {bus_target}: sync timeout")
        self.store = ReplicaStoreFacade(self._replica)
        self.proxy = _RemoteProxyChain(self.store, proxy_target, token)

    def close(self) -> None:
        self._replica.close()

    def __enter__(self) -> "RemotePlane":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def cmd_init(**kw) -> ControlPlane:
    """Bootstrap a control plane (karmadactl init / operator install)."""
    from .controlplane import ControlPlane

    return ControlPlane(**kw)


def cmd_local_up(n_members: int = 3, **kw) -> ControlPlane:
    """hack/local-up-karmada.sh: control plane + n members (last one Pull)."""
    cp = cmd_init(**kw)
    for i in range(1, n_members + 1):
        cluster = new_cluster(f"member{i}", cpu="100", memory="200Gi")
        if i == n_members and n_members >= 3:
            cluster.spec.sync_mode = PULL
        cp.join_cluster(cluster)
    cp.settle()
    return cp


def cmd_join(
    cp: ControlPlane, name: str, member: Optional[MemberCluster] = None, **cluster_kw
) -> Cluster:
    """Push-mode join (pkg/karmadactl/join)."""
    cluster = new_cluster(name, **cluster_kw)
    cp.join_cluster(cluster, member)
    return cluster


def cmd_deinit(cp: ControlPlane) -> None:
    """Tear the control plane down (pkg/karmadactl/cmdinit deinit): unjoin
    every member (draining execution spaces), then drop all control-plane
    state so the instance can be garbage collected."""
    for name in list(cp.members.names()):
        cp.unjoin_cluster(name)
    cp.settle()
    for kind in list(cp.store.kinds()):
        for obj in list(cp.store.list(kind)):
            cp.store.delete(kind, obj.meta.namespaced_name)


def cmd_unjoin(cp: ControlPlane, name: str) -> None:
    cp.unjoin_cluster(name)


def cmd_token_create(cp: ControlPlane) -> str:
    """karmadactl token create: bootstrap token for pull-mode registration."""
    return cp.authority.create_token().token


def cmd_register(
    cp: ControlPlane,
    name: str,
    member: Optional[MemberCluster] = None,
    token: Optional[str] = None,
    **cluster_kw,
) -> Cluster:
    """Pull-mode register (pkg/karmadactl/register): kubeadm-style token ->
    CSR -> signed agent cert, then deploys the agent. Without a token the
    admin-kubeconfig path is used (direct join)."""
    if token is not None:
        record = cp.authority.submit_csr(name, token)
        if record is None:
            raise PermissionError(f"invalid or expired bootstrap token for {name}")
    cluster = new_cluster(name, **cluster_kw)
    cluster.spec.sync_mode = PULL
    cp.join_cluster(cluster, member)
    return cluster


def cmd_unregister(cp: ControlPlane, name: str) -> None:
    cp.unjoin_cluster(name)


def cmd_cordon(cp: ControlPlane, name: str) -> None:
    """Mark a cluster unschedulable (pkg/karmadactl/cordon)."""
    cluster = cp.store.get("Cluster", name)
    if cluster is None:
        raise KeyError(name)
    if not any(t.key == CORDON_TAINT_KEY for t in cluster.spec.taints):
        cluster.spec.taints.append(Taint(key=CORDON_TAINT_KEY, effect=NO_SCHEDULE))
        cp.store.apply(cluster)


def cmd_uncordon(cp: ControlPlane, name: str) -> None:
    cluster = cp.store.get("Cluster", name)
    if cluster is None:
        raise KeyError(name)
    before = len(cluster.spec.taints)
    cluster.spec.taints = [
        t for t in cluster.spec.taints if t.key != CORDON_TAINT_KEY
    ]
    if len(cluster.spec.taints) != before:
        cp.store.apply(cluster)


def cmd_taint(
    cp: ControlPlane, name: str, key: str, value: str = "", effect: str = NO_SCHEDULE,
    remove: bool = False,
) -> None:
    """pkg/karmadactl/cordon taint command analogue."""
    cluster = cp.store.get("Cluster", name)
    if cluster is None:
        raise KeyError(name)
    cluster.spec.taints = [
        t for t in cluster.spec.taints if not (t.key == key and t.effect == effect)
    ]
    if not remove:
        cluster.spec.taints.append(Taint(key=key, value=value, effect=effect))
    cp.store.apply(cluster)


def cmd_get(
    cp: ControlPlane,
    gvk: str,
    namespace: str = "",
    name: str = "",
    cluster: Optional[str] = None,
    labels: Optional[dict] = None,
):
    """Multi-cluster get/list through the proxy chain."""
    verb = "get" if name else "list"
    return cp.proxy.connect(
        _proxy_request(
            verb=verb, gvk=gvk, namespace=namespace, name=name,
            cluster=cluster, labels=dict(labels or {}),
        )
    )


def cmd_describe(cp: ControlPlane, gvk: str, namespace: str, name: str) -> str:
    """Aggregated description: template + binding + per-cluster status."""
    lines = [f"{gvk} {namespace}/{name}"]
    resp = cmd_get(cp, gvk, namespace, name)
    if resp.obj is None:
        return f"{gvk} {namespace}/{name}: not found"
    kind = gvk.rsplit("/", 1)[-1].lower()
    rb = cp.store.get(
        "ResourceBinding",
        f"{namespace}/{name}-{kind}" if namespace else f"{name}-{kind}",
    )
    if rb is not None:
        lines.append("placements:")
        for tc in rb.spec.clusters:
            lines.append(f"  {tc.name}: {tc.replicas} replicas")
        for item in rb.status.aggregated_status:
            lines.append(
                f"  {item.cluster_name}: applied={item.applied} health={item.health}"
            )
    return "\n".join(lines)


def cmd_top(cp: ControlPlane, workload_key: str):
    """Per-cluster + merged utilization (pkg/karmadactl/top)."""
    if cp.metrics_adapter is None:
        raise RuntimeError(
            "metrics adapter not installed (enable the "
            "karmada-metrics-adapter addon)"
        )
    samples = cp.metrics_adapter.resource_metrics(workload_key)
    merged = cp.metrics_adapter.merged_utilization(workload_key)
    return {"clusters": {s.cluster: s.value for s in samples}, "merged": merged}


def cmd_promote(
    cp: ControlPlane, cluster_name: str, gvk: str, namespace: str, name: str
) -> None:
    """Import an existing member-cluster resource into the control plane as a
    template + policy pinned to that cluster (pkg/karmadactl/promote)."""
    member = (
        cp.members.get(cluster_name) if hasattr(cp, "members") else None
    )
    if member is not None:
        obj = member.get(gvk, namespace, name)
    else:
        # remote plane: fetch the live object through the cluster proxy
        resp = cp.proxy.connect(
            _proxy_request(
                verb="get", gvk=gvk, namespace=namespace, name=name,
                cluster=cluster_name,
            )
        )
        obj = resp.obj if not resp.error else None
    if obj is None:
        raise KeyError(f"{gvk} {namespace}/{name} not found in {cluster_name}")
    import copy

    template = copy.deepcopy(obj)
    template.meta.resource_version = 0
    cp.store.apply(template)
    api_version, _, kind = gvk.rpartition("/")
    cp.store.apply(
        PropagationPolicy(
            meta=ObjectMeta(name=f"promote-{name}", namespace=namespace),
            spec=PropagationSpec(
                resource_selectors=[
                    ResourceSelector(
                        api_version=api_version, kind=kind,
                        namespace=namespace, name=name,
                    )
                ],
                placement=Placement(
                    cluster_affinity=ClusterAffinity(cluster_names=[cluster_name])
                ),
                # seamless takeover: adopt the live member object instead of
                # refusing on conflict (promote.go:738-798 sets Overwrite on
                # both the policy and the resource annotation)
                conflict_resolution="Overwrite",
            ),
        )
    )


def cmd_interpret(cp: ControlPlane, template, operation: str, **kw):
    """Dry-run an interpreter operation (pkg/karmadactl/interpret)."""
    interp = cp.interpreter
    if operation == "GetReplicas":
        return interp.get_replicas(template)
    if operation == "ReviseReplica":
        return interp.revise_replica(template, kw["replicas"])
    if operation == "InterpretHealth":
        return interp.interpret_health(template)
    if operation == "ReflectStatus":
        return interp.reflect_status(template)
    if operation == "GetDependencies":
        return interp.get_dependencies(template)
    if operation == "AggregateStatus":
        return interp.aggregate_status(template, kw.get("items", []))
    raise ValueError(f"unknown operation {operation}")


def cmd_logs(
    cp: ControlPlane,
    cluster: str,
    namespace: str,
    pod: str,
    tail: Optional[int] = None,
) -> list[str]:
    """karmadactl logs: pod logs through the clusters/{name}/proxy
    passthrough (pkg/karmadactl/logs)."""
    resp = cp.proxy.connect(
        _proxy_request(
            verb="logs", gvk="v1/Pod", namespace=namespace, name=pod,
            cluster=cluster, options={"tail": tail},
        )
    )
    if resp.error:
        raise RuntimeError(resp.error)
    return resp.data


def cmd_exec(
    cp: ControlPlane, cluster: str, namespace: str, pod: str, command: list[str]
) -> dict:
    """karmadactl exec: run a command in a member pod via the proxy
    (pkg/karmadactl/exec)."""
    resp = cp.proxy.connect(
        _proxy_request(
            verb="exec", gvk="v1/Pod", namespace=namespace, name=pod,
            cluster=cluster, options={"command": list(command)},
        )
    )
    if resp.error:
        raise RuntimeError(resp.error)
    return resp.data


def cmd_attach(
    cp: ControlPlane, cluster: str, namespace: str, pod: str
) -> list[str]:
    """karmadactl attach: stream the pod's output (pkg/karmadactl/attach) —
    in-proc this is the log stream from the runtime seam."""
    return cmd_logs(cp, cluster, namespace, pod)


ADDONS = (
    "karmada-descheduler",
    "karmada-scheduler-estimator",
    "karmada-search",
    "karmada-metrics-adapter",
)


def cmd_addons(cp: ControlPlane, enable: Sequence[str] = (), disable: Sequence[str] = ()):
    """Toggle optional components (pkg/karmadactl/addons: estimator,
    descheduler, search, metrics-adapter)."""
    from .controllers import Descheduler
    from .metricsadapter import MetricsAdapter

    state = {}
    for name in enable:
        if name not in ADDONS:
            raise ValueError(f"unknown addon {name}")
        if name == "karmada-descheduler":
            if cp.descheduler is None:
                cp.descheduler = Descheduler(
                    cp.store, cp.runtime, cp.members, clock=cp.clock
                )
            cp.descheduler.active = True
        elif name == "karmada-scheduler-estimator":
            cp.enable_accurate_estimators()
        elif name == "karmada-metrics-adapter" and cp.metrics_adapter is None:
            cp.metrics_adapter = MetricsAdapter(cp.members)
        elif name == "karmada-search":
            cp.search.resync()
        state[name] = "enabled"
    for name in disable:
        if name not in ADDONS:
            raise ValueError(f"unknown addon {name}")
        if name == "karmada-descheduler":
            # the ticker registration is permanent; deactivate in place so
            # disable actually stops reclaim and re-enable can't double-tick
            if cp.descheduler is not None:
                cp.descheduler.active = False
        elif name == "karmada-scheduler-estimator":
            cp.disable_accurate_estimators()
        elif name == "karmada-metrics-adapter":
            cp.metrics_adapter = None
        elif name == "karmada-search":
            cp.search.disable()
        state[name] = "disabled"
    return state


# --------------------------------------------------------------------------
# generic resource verbs (ref: pkg/karmadactl/karmadactl.go:98-178 — the
# kubectl-style apply/delete/patch/label/annotate/api-resources surface;
# subdirs pkg/karmadactl/{apply,patch,...}). Every verb runs over a
# ControlPlane-SHAPED handle: in-proc cp or RemotePlane — remote writes
# ride the store bus and the PLANE's admission chain validates them
# server-side, exactly like kubectl hitting the aggregated apiserver.
# --------------------------------------------------------------------------


def _load_manifests(text: str) -> list[dict]:
    """Parse manifests: a JSON object, a JSON array, a {kind: List,
    items: [...]} envelope, or (when available) multi-document YAML."""
    text = text.strip()
    docs: list = []
    if text.startswith(("{", "[")):
        data = json.loads(text)
        docs = data if isinstance(data, list) else [data]
    else:
        try:
            import yaml  # type: ignore[import-not-found]
        except ImportError as exc:  # JSON-only environment
            raise ValueError(
                "manifest is not JSON and no YAML parser is available"
            ) from exc
        docs = [d for d in yaml.safe_load_all(text) if d]
    out: list[dict] = []
    for d in docs:
        if isinstance(d, dict) and d.get("kind") == "List":
            out.extend(d.get("items") or [])
        else:
            out.append(d)
    return out


def _manifest_to_obj(manifest: dict):
    """k8s-style manifest -> typed object. Kinds the bus codec knows
    (karmada-native CRs) decode through the registry (metadata -> meta);
    anything else becomes a template ``Resource`` — the store's workload
    representation (what the detector matches policies against)."""
    from .bus.service import kind_registry
    from .utils.codec import from_jsonable

    kind = manifest.get("kind", "")
    reg = kind_registry()
    if kind in reg and kind != "Resource":
        from .api.versioning import maybe_upgrade

        manifest = maybe_upgrade(kind, manifest)
        d = {k: v for k, v in manifest.items() if k not in (
            "apiVersion", "kind",
        )}
        if "metadata" in d and "meta" not in d:
            d["meta"] = d.pop("metadata")
        return from_jsonable(reg[kind], d)
    from .interpreter.webhook import resource_from_dict

    return resource_from_dict(manifest)


def _resolve(cp, kind: str, namespace: str, name: str):
    """(store_kind, key, obj) for a verb target. ``kind`` is a registry
    kind ("PropagationPolicy"), or a gvk ("apps/v1/Deployment") / bare
    workload kind ("Deployment") for template Resources."""
    from .bus.service import kind_registry

    key = f"{namespace}/{name}" if namespace else name
    if "/" not in kind and kind in kind_registry() and kind != "Resource":
        return kind, key, cp.store.get(kind, key)
    obj = cp.store.get("Resource", key)
    if obj is not None and "/" in kind:
        if f"{obj.api_version}/{obj.kind}" != kind:
            return "Resource", key, None
    elif obj is not None and kind not in ("", "Resource", obj.kind):
        return "Resource", key, None
    return "Resource", key, obj


def _merge_patch(doc, patch):
    """RFC 7386 JSON merge patch (kubectl patch --type=merge)."""
    if not isinstance(patch, dict):
        return patch
    out = dict(doc) if isinstance(doc, dict) else {}
    for k, v in patch.items():
        if v is None:
            out.pop(k, None)
        else:
            out[k] = _merge_patch(out.get(k), v)
    return out


def cmd_apply(cp, manifests: Sequence[dict]) -> list[str]:
    """Create-or-update each manifest through the (possibly remote) store;
    the plane's admission chain validates server-side."""
    from .utils.store import obj_key, obj_kind

    applied = []
    for m in manifests:
        obj = _manifest_to_obj(m)
        cp.store.apply(obj)
        applied.append(f"{obj_kind(obj)}/{obj_key(obj)}")
    return applied


def cmd_delete(
    cp, kind: str, namespace: str, name: str, *, force: bool = False
) -> bool:
    store_kind, key, obj = _resolve(cp, kind, namespace, name)
    if obj is None:
        return False
    return bool(cp.store.delete(store_kind, key, force=force))


def cmd_patch(
    cp, kind: str, namespace: str, name: str, patch, patch_type: str = "merge"
):
    """Patch an object: ``merge`` (RFC 7386) or ``json`` (RFC 6902 ops).
    Spec changes bump the generation, mirroring the apiserver contract
    controllers reconcile against."""
    from .bus.service import decode_object
    from .interpreter.webhook import apply_json_patch
    from .utils.codec import to_jsonable

    store_kind, key, obj = _resolve(cp, kind, namespace, name)
    if obj is None:
        raise KeyError(f"{kind} {key} not found")
    doc = to_jsonable(obj)
    if patch_type == "merge":
        patched = _merge_patch(doc, patch)
    elif patch_type == "json":
        patched = apply_json_patch(doc, patch)
    else:
        raise ValueError(f"unknown patch type {patch_type!r}")
    new = decode_object(store_kind, json.dumps(patched))
    if to_jsonable(new).get("spec") != doc.get("spec"):
        new.meta.generation = obj.meta.generation + 1
    cp.store.apply(new)  # remote facades return the rv, not the object
    return new


def _mutate_meta_map(
    cp, kind: str, namespace: str, name: str, changes: Sequence[str],
    attr: str,
):
    from .bus.service import decode_object, encode_object

    store_kind, key, obj = _resolve(cp, kind, namespace, name)
    if obj is None:
        raise KeyError(f"{kind} {key} not found")
    # work on a codec round-trip COPY: store/mirror gets return the live
    # object, and mutating it before apply would make a rejected write
    # visible anyway (and defeat old-vs-new comparison in-proc)
    obj = decode_object(store_kind, encode_object(obj))
    mapping = dict(getattr(obj.meta, attr))
    for ch in changes:
        if ch.endswith("-") and "=" not in ch:
            mapping.pop(ch[:-1], None)
        else:
            k, sep, v = ch.partition("=")
            if not sep:
                raise ValueError(f"expected KEY=VALUE or KEY-, got {ch!r}")
            mapping[k] = v
    setattr(obj.meta, attr, mapping)
    cp.store.apply(obj)  # remote facades return the rv, not the object
    return obj


def cmd_create(cp, manifests: Sequence[dict]) -> list[str]:
    """Create-only write (karmadactl create / kubectl create): unlike
    ``apply`` an existing object is an AlreadyExists error, not an update.
    Ref: pkg/karmadactl/karmadactl.go:98-178 (create verb wiring)."""
    from .utils.store import obj_key, obj_kind

    created = []
    objs = []
    seen: set = set()
    for m in manifests:
        obj = _manifest_to_obj(m)
        kind, key = obj_kind(obj), obj_key(obj)
        # batch-wide existence precheck (catches duplicates WITHIN the
        # file too) before the first write; admission still runs per
        # apply, so like kubectl an admission rejection mid-file reports
        # what was already created rather than rolling it back
        if (kind, key) in seen or cp.store.get(kind, key) is not None:
            raise ValueError(f"{kind} {key!r} already exists")
        seen.add((kind, key))
        objs.append((obj, f"{kind}/{key}"))
    for obj, ref in objs:
        try:
            cp.store.apply(obj)
        except Exception as exc:
            raise ValueError(
                f"{ref} rejected: {exc}"
                + (f" (already created: {', '.join(created)})" if created else "")
            ) from exc
        created.append(ref)
    return created


def cmd_edit(cp, kind: str, namespace: str, name: str, *, editor=None):
    """kubectl-style edit: dump the object to a temp file, run the user's
    editor on it, apply the result if it changed. ``editor`` is the command
    line (defaults to $KUBE_EDITOR / $EDITOR / vi, as kubectl resolves it);
    returns the applied object or None when the buffer was left unchanged.
    Ref: pkg/karmadactl/edit/edit.go (NewCmdEdit wraps kubectl's editor
    flow against the karmada control plane)."""
    import os
    import shlex
    import subprocess
    import tempfile

    from .bus.service import decode_object
    from .utils.codec import to_jsonable

    store_kind, key, obj = _resolve(cp, kind, namespace, name)
    if obj is None:
        raise KeyError(f"{kind} {key} not found")
    doc = to_jsonable(obj)
    text = json.dumps(doc, indent=2, sort_keys=True)
    ed = (
        editor
        or os.environ.get("KUBE_EDITOR")
        or os.environ.get("EDITOR")
        or "vi"
    )
    fd, path = tempfile.mkstemp(suffix=".json", prefix="karmadactl-edit-")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
        subprocess.run(f"{ed} {shlex.quote(path)}", shell=True, check=True)
        with open(path) as f:
            new_text = f.read()
        new_doc = json.loads(new_text)
        if new_doc == doc:
            os.unlink(path)
            return None  # "Edit cancelled, no changes made."
        # identity is immutable under edit (kubectl rejects primitive
        # changes): a changed name/namespace/kind would silently CREATE a
        # new object under another store key, leaving the edited one as-is
        for field, depth in (("kind", ()), ("name", ("meta",)),
                             ("namespace", ("meta",))):
            old_v, new_v = doc, new_doc
            for seg in depth:
                old_v = (old_v or {}).get(seg)
                new_v = (new_v or {}).get(seg)
            if (old_v or {}).get(field) != (new_v or {}).get(field):
                raise ValueError(
                    f"edit may not change {'.'.join(depth + (field,))}"
                )
        new = decode_object(store_kind, json.dumps(new_doc))
        # canonical-form comparison, same as cmd_patch: a key the codec
        # discards must not bump generation / wake controllers
        if to_jsonable(new).get("spec") != doc.get("spec"):
            new.meta.generation = obj.meta.generation + 1
        cp.store.apply(new)
    except Exception:
        # a post-editor failure (parse error, identity change, admission
        # rejection) must NOT destroy the user's edits: keep the buffer
        # and report where it lives, as kubectl does
        print(f"edit buffer preserved at {path}", file=sys.stderr)
        raise
    else:
        os.unlink(path)
    return new


def cmd_explain(path: str) -> str:
    """Field documentation for an API kind (karmadactl explain). The
    reference serves this from the apiserver's OpenAPI schema
    (pkg/karmadactl/explain/); here the registry's dataclasses ARE the
    schema, so explain reflects over them — same dotted-path grammar
    (``PropagationPolicy.spec.placement``), offline."""
    import dataclasses
    import typing

    from .bus.service import kind_registry

    kind, _, rest = path.partition(".")
    reg = kind_registry()
    cls = reg.get(kind)
    if cls is None:
        known = ", ".join(sorted(reg))
        raise KeyError(f"unknown kind {kind!r}; served kinds: {known}")

    import types as _types

    def unwrap(tp):
        """Optional[X] -> X; list[X]/dict[K,V] pass through for display."""
        origin = typing.get_origin(tp)
        if origin is typing.Union or origin is _types.UnionType:
            args = [a for a in typing.get_args(tp) if a is not type(None)]
            if len(args) == 1:
                return unwrap(args[0])
        return tp

    def type_name(tp) -> str:
        tp = unwrap(tp)
        origin = typing.get_origin(tp)
        if origin in (list, dict):
            args = ", ".join(type_name(a) for a in typing.get_args(tp))
            return f"{origin.__name__}[{args}]"
        return getattr(tp, "__name__", str(tp))

    def element(tp):
        """The dataclass to descend into (through Optional/list/dict)."""
        tp = unwrap(tp)
        origin = typing.get_origin(tp)
        if origin is list:
            return element(typing.get_args(tp)[0])
        if origin is dict:
            return element(typing.get_args(tp)[1])
        return tp if dataclasses.is_dataclass(tp) else None

    # descend the dotted path
    walked = [kind]
    for seg in [s for s in rest.split(".") if s]:
        if not dataclasses.is_dataclass(cls):
            raise KeyError(
                f"{'.'.join(walked)} is a scalar ({type_name(cls)}); "
                f"cannot descend into {seg!r}"
            )
        hints = typing.get_type_hints(cls)
        match = next(
            (f for f in dataclasses.fields(cls) if f.name == seg), None
        )
        if match is None:
            have = ", ".join(f.name for f in dataclasses.fields(cls))
            raise KeyError(
                f"field {seg!r} does not exist in {'.'.join(walked)}; "
                f"fields: {have}"
            )
        nxt = element(hints[match.name])
        cls = nxt if nxt is not None else unwrap(hints[match.name])
        walked.append(seg)

    lines = [f"KIND:     {kind}", f"PATH:     {'.'.join(walked)}", ""]
    doc = (getattr(cls, "__doc__", "") or "").strip().splitlines()
    if doc:
        lines += ["DESCRIPTION:", f"     {doc[0]}", ""]
    if dataclasses.is_dataclass(cls):
        lines.append("FIELDS:")
        hints = typing.get_type_hints(cls)
        for f in dataclasses.fields(cls):
            tn = type_name(hints[f.name])
            mark = " <required>" if (
                f.default is dataclasses.MISSING
                and f.default_factory is dataclasses.MISSING
            ) else ""
            lines.append(f"   {f.name}\t<{tn}>{mark}")
    else:
        lines.append(f"TYPE:     {type_name(cls)}")
    return "\n".join(lines)


def cmd_completion(shell: str = "bash") -> str:
    """Shell completion script generated from the live parser (karmadactl
    completion; ref pkg/karmadactl/karmadactl.go — cobra emits these).
    Bash and zsh (via bashcompinit) share the emitted script."""
    if shell not in ("bash", "zsh"):
        raise ValueError(f"unsupported shell {shell!r} (bash or zsh)")
    parser, sub = build_parser()
    cmds = sorted(sub.choices)
    # global flags reflected from the live parser, like the per-subcommand
    # ones — a new top-level flag must not be invisible to completion
    global_flags = sorted(
        opt
        for a in parser._actions
        for opt in a.option_strings
        if opt.startswith("--")
    )
    flag_lines = []
    for name, sp in sorted(sub.choices.items()):
        flags = sorted(
            opt
            for a in sp._actions
            for opt in a.option_strings
            if opt.startswith("--")
        )
        flag_lines.append(f'    {name}) opts="{" ".join(flags)}" ;;')
    body = "\n".join(flag_lines)
    # value-taking global flags: the word AFTER one is its value, not the
    # subcommand (``--bus host:1234 apply`` must resolve cmd=apply)
    valued = sorted(
        opt
        for a in parser._actions
        for opt in a.option_strings
        # store_true / help have nargs == 0; plain store has nargs None
        if opt.startswith("--") and a.nargs != 0
    )
    zsh_boot = (
        "autoload -U +X bashcompinit && bashcompinit\n"
        "autoload -U +X compinit && compinit\n"
        if shell == "zsh"
        else ""
    )
    return f"""# karmadactl-tpu completion ({shell}); source this file
{zsh_boot}_karmadactl_tpu() {{
  local cur cmd opts skip
  COMPREPLY=()
  cur="${{COMP_WORDS[COMP_CWORD]}}"
  cmd=""
  skip=0
  for w in "${{COMP_WORDS[@]:1:COMP_CWORD-1}}"; do
    if [ "$skip" = 1 ]; then skip=0; continue; fi
    case "$w" in
      {'|'.join(valued)}) skip=1 ;;
      -*) ;;
      *) cmd="$w"; break ;;
    esac
  done
  if [ -z "$cmd" ]; then
    COMPREPLY=( $(compgen -W "{' '.join(cmds)} {' '.join(global_flags)}" -- "$cur") )
    return 0
  fi
  case "$cmd" in
{body}
    *) opts="" ;;
  esac
  COMPREPLY=( $(compgen -W "$opts" -- "$cur") )
  return 0
}}
complete -F _karmadactl_tpu karmadactl-tpu
"""


def cmd_label(cp, kind, namespace, name, changes):
    """kubectl-style label mutation: KEY=VALUE adds/overwrites, KEY-
    removes."""
    return _mutate_meta_map(cp, kind, namespace, name, changes, "labels")


def cmd_annotate(cp, kind, namespace, name, changes):
    return _mutate_meta_map(cp, kind, namespace, name, changes, "annotations")


#: kinds stored by bare name (no namespace segment in the store key) —
#: discovery must say so or clients will address them as ns/name
_CLUSTER_SCOPED = {
    "Cluster", "ClusterPropagationPolicy", "ClusterOverridePolicy",
    "ClusterResourceBinding", "ResourceRegistry", "Remedy",
    "ClusterTaintPolicy", "Karmada", "ResourceInterpreterCustomization",
    "ResourceInterpreterWebhookConfiguration", "WorkloadRebalancer",
}


def _format_get(doc, output: str, gvk: str) -> str:
    """kubectl -o rendering for get results. ``doc`` is either one
    jsonable object or a list of {cluster, object} rows."""
    rows = doc if isinstance(doc, list) else [{"cluster": "", "object": doc}]

    def meta(o):
        return o.get("meta") or o.get("metadata") or {}

    if output == "yaml":
        import yaml

        return yaml.safe_dump(doc, sort_keys=False).rstrip()
    if output == "name":
        kind = gvk.rsplit("/", 1)[-1].lower()
        return "\n".join(
            f"{kind}/{meta(r['object']).get('name', '')}" for r in rows
        )
    if output == "wide":
        # kubectl's wide table, multi-cluster flavored: one line per
        # (cluster, object) with the status fields the aggregated
        # deployment view carries
        out = [f"{'CLUSTER':16} {'NAMESPACE':12} {'NAME':24} "
               f"{'READY':8} {'GENERATION':10}"]
        for r in rows:
            o = r["object"]
            m = meta(o)
            st = o.get("status") or {}
            ready = (
                f"{st.get('readyReplicas', st.get('ready_replicas', 0))}"
                f"/{(o.get('spec') or {}).get('replicas', '-')}"
            )
            out.append(
                f"{r.get('cluster', '') or '-':16} "
                f"{m.get('namespace', '') or '-':12} "
                f"{m.get('name', ''):24} {ready:8} "
                f"{m.get('generation', 0):<10}"
            )
        return "\n".join(out)
    return json.dumps(doc)


def cmd_api_resources(cp) -> list[dict]:
    """The discovery surface (karmadactl api-resources): registry kinds
    plus the proxied workload plurals."""
    from .bus.service import kind_registry
    from .search.proxyserver import _PLURALS

    out = [
        {"kind": k, "namespaced": k not in _CLUSTER_SCOPED,
         "source": "karmada"}
        for k in sorted(kind_registry())
    ]
    out += [
        {"kind": gvk, "plural": plural, "source": "cluster-proxy"}
        for plural, gvk in sorted(_PLURALS.items())
    ]
    return out


def build_parser() -> tuple:
    """The argparse surface, shared by ``main`` and ``cmd_completion``.
    Returns (parser, subparsers)."""
    parser = argparse.ArgumentParser(prog="karmadactl-tpu")
    parser.add_argument("--bus", default="", help="remote plane bus host:port")
    parser.add_argument("--proxy", default="", help="cluster proxy host:port")
    parser.add_argument("--token", default="admin-token")
    sub = parser.add_subparsers(dest="command", required=True)

    lu = sub.add_parser("local-up", help="bootstrap a demo control plane")
    lu.add_argument("--members", type=int, default=3)
    lu.add_argument(
        "--processes", action="store_true",
        help="spawn plane/solver/estimator/agent as separate OS processes "
        "(hack/local-up-karmada.sh analogue) and stay up",
    )

    g = sub.add_parser("get", help="multi-cluster get/list")
    g.add_argument("gvk")
    g.add_argument("--namespace", default="default")
    g.add_argument("--name", default="")
    g.add_argument("--cluster", default="")
    g.add_argument("-l", "--selector", default="",
                   help="label selector: key=value[,key2=value2]")
    g.add_argument("-o", "--output", default="json",
                   choices=("json", "yaml", "name", "wide"))

    d = sub.add_parser("describe", help="aggregated describe")
    d.add_argument("gvk")
    d.add_argument("namespace")
    d.add_argument("name")

    lg = sub.add_parser("logs", help="pod logs via the cluster proxy")
    lg.add_argument("cluster")
    lg.add_argument("namespace")
    lg.add_argument("pod")
    lg.add_argument("--tail", type=int, default=None)

    for nm in ("cordon", "uncordon"):
        cd = sub.add_parser(nm, help=f"{nm} a cluster")
        cd.add_argument("name")

    tn = sub.add_parser("taint", help="taint a cluster")
    tn.add_argument("name")
    tn.add_argument("key")
    tn.add_argument("--value", default="")
    tn.add_argument("--effect", default=NO_SCHEDULE)
    tn.add_argument("--remove", action="store_true")

    pm = sub.add_parser("promote", help="import a member resource")
    pm.add_argument("cluster")
    pm.add_argument("gvk")
    pm.add_argument("namespace")
    pm.add_argument("name")

    ap = sub.add_parser("apply", help="apply manifests through the bus")
    ap.add_argument("-f", "--filename", required=True,
                    help="manifest file (JSON/YAML; '-' = stdin)")

    cr = sub.add_parser("create", help="create-only apply through the bus")
    cr.add_argument("-f", "--filename", required=True,
                    help="manifest file (JSON/YAML; '-' = stdin)")

    ed = sub.add_parser("edit", help="edit a resource in $EDITOR")
    ed.add_argument("kind")
    ed.add_argument("namespace")
    ed.add_argument("name")
    ed.add_argument("--editor", default=None,
                    help="editor command (default: $KUBE_EDITOR / $EDITOR)")

    ex = sub.add_parser(
        "explain",
        help="field docs for a served kind (KIND[.field...]), or — with "
        "a <ns>/<name> argument — the binding's placement decision "
        "chain from the provenance plane (/debug/explain): per-stage "
        "exclusion reasons, the selected affinity group, top-k "
        "candidates and the final assignment",
    )
    ex.add_argument(
        "path",
        help="KIND[.field.subfield...] for field docs, or <ns>/<name> "
        "for a placement explanation",
    )
    ex.add_argument(
        "--wave", type=int, default=None,
        help="pin the placement explanation to one wave id "
        "(default: the newest capture holding the binding)",
    )
    ex.add_argument(
        "--metrics", default="",
        help="HOST:PORT of the scheduling process's metrics endpoint; "
        "without it the CURRENT process's in-proc ExplainStore answers "
        "(useful under an embedded plane)",
    )
    ex.add_argument(
        "--json", dest="as_json", action="store_true",
        help="print the raw explanation document instead of the "
        "decision-chain view",
    )

    co = sub.add_parser("completion", help="emit a shell completion script")
    co.add_argument("shell", nargs="?", default="bash",
                    choices=("bash", "zsh"))

    dl = sub.add_parser("delete", help="delete a resource through the bus")
    dl.add_argument("kind", help="registry kind or workload gvk")
    dl.add_argument("namespace")
    dl.add_argument("name")
    dl.add_argument("--force", action="store_true",
                    help="bypass finalizer gating")

    pt = sub.add_parser("patch", help="patch a resource through the bus")
    pt.add_argument("kind")
    pt.add_argument("namespace")
    pt.add_argument("name")
    pt.add_argument("-p", "--patch", required=True,
                    help="patch document (JSON)")
    pt.add_argument("--type", dest="patch_type", default="merge",
                    choices=("merge", "json"))

    for nm in ("label", "annotate"):
        mu = sub.add_parser(nm, help=f"{nm} a resource through the bus")
        mu.add_argument("kind")
        mu.add_argument("namespace")
        mu.add_argument("name")
        mu.add_argument("changes", nargs="+",
                        help="KEY=VALUE to set, KEY- to remove")

    sub.add_parser("api-resources", help="discovery: served kinds")

    wu = sub.add_parser(
        "warmup",
        help="AOT-prewarm the scheduler's XLA traces from the trace "
        "manifest (kills the plane's cold start; run before serving or "
        "after deploying a new build)",
    )
    wu.add_argument(
        "--manifest", default="",
        help="trace-manifest path (default: KARMADA_TPU_TRACE_MANIFEST, "
        "else <cache dir>/trace_manifest.json)",
    )
    wu.add_argument(
        "--no-expand", action="store_true",
        help="compile only observed signatures (skip the next-bucket "
        "cap expansion)",
    )

    tr = sub.add_parser(
        "trace",
        help="wave-trace operations: `trace dump --metrics HOST:PORT` "
        "fetches /debug/traces from a running process (plane, solver, "
        "estimator, bus — any MetricsServer) and prints the span ring + "
        "per-wave phase summaries as JSON; `trace dump --stitch` "
        "additionally pulls every registered peer's ring and merges the "
        "cross-process wave trees (per-process + per-channel columns); "
        "`trace analyze RECORD` re-renders a flight-recorder JSONL "
        "record's attribution offline",
    )
    tr.add_argument("action", choices=("dump", "analyze"))
    tr.add_argument(
        "record", nargs="?", default="",
        help="flight-recorder JSONL path (trace analyze)",
    )
    tr.add_argument(
        "--metrics", default="",
        help="HOST:PORT of the target process's metrics endpoint; "
        "without it the CURRENT process's in-proc tracer dumps (useful "
        "under an embedded plane)",
    )
    tr.add_argument(
        "--wave", type=int, default=None,
        help="restrict the span dump to one wave id (dump), or pick the "
        "flight record for that wave (analyze; default: the last record)",
    )
    tr.add_argument(
        "--summary", action="store_true",
        help="print only the per-wave phase summaries",
    )
    tr.add_argument(
        "--stitch", action="store_true",
        help="pull /debug/traces from every peer (--peers, the dumped "
        "process's registered peers, or KARMADA_TPU_TRACE_PEERS) and "
        "merge the cross-process wave trees",
    )
    tr.add_argument(
        "--peers", default="",
        help="comma-separated name=host:port peer metrics endpoints for "
        "--stitch (overrides the dumped process's registry)",
    )

    tp = sub.add_parser(
        "top",
        help="plane-wide per-wave telemetry table from the history rings "
        "(`/debug/history`): latest wave per process (wall, coverage, "
        "bindings/s, rows packed/replayed, compiles, upload/fetch MB, "
        "per-channel RPCs, device bytes, queue depth) plus "
        "recent-window p50/p95 digests and live settle-latency "
        "quantiles off /metrics; `--watch` refreshes in place",
    )
    tp.add_argument(
        "--metrics", default="",
        help="HOST:PORT of a process's metrics endpoint; without it the "
        "CURRENT process's in-proc history answers (useful under an "
        "embedded plane)",
    )
    tp.add_argument(
        "--peers", default="",
        help="comma-separated name=host:port peer metrics endpoints "
        "(default: the target's registered peers, else "
        "KARMADA_TPU_TRACE_PEERS)",
    )
    tp.add_argument(
        "--window", type=int, default=64,
        help="history rows fetched per process (digests cover the same "
        "window)",
    )
    tp.add_argument("--watch", action="store_true",
                    help="refresh every --interval seconds until Ctrl-C")
    tp.add_argument("--interval", type=float, default=2.0)
    tp.add_argument("--json", dest="as_json", action="store_true",
                    help="print the raw aggregated document instead of "
                    "the table")

    qu = sub.add_parser(
        "quota",
        help="quota-plane operations: `quota status [--metrics HOST:PORT]` "
        "prints per-namespace limit/used/denied from the metrics endpoint "
        "(karmada_tpu_quota_limit / _used / _denied_total families)",
    )
    qu.add_argument("action", choices=("status",))
    qu.add_argument(
        "--metrics", default="",
        help="HOST:PORT of the plane's metrics endpoint; without it the "
        "CURRENT process's in-proc registry answers (useful under an "
        "embedded plane)",
    )

    li = sub.add_parser(
        "lint",
        help="run graftlint, the repo's two-tier static analyzer: AST "
        "tier (GL001 trace safety, GL002 trace-key completeness, GL003 "
        "env-flag registry, GL004 lock discipline, GL005 import hygiene, "
        "GL006 metric naming, GL007 bounded RPCs, GL008 span taxonomy, "
        "GL009 history series sources, GL010 reason taxonomy, GL011 "
        "lock-read discipline, GL012 budget-in-loop, GL013 bounded "
        "caches), with --ir the jaxpr-level kernel auditor (IR001 dtype "
        "discipline, IR002 host round-trips, IR003 const capture, IR004 "
        "trace-manifest fidelity, IR005 donation audit), with --dep the "
        "row-dependence certifier (IR006 row_coupled declarations, IR007 "
        "replicated-scan discipline), and with --all every tier at once",
    )
    li.add_argument(
        "paths", nargs="*",
        help="files/directories to lint (default: karmada_tpu tools); "
        "with --ir, kernel family names to audit (default: all)",
    )
    li.add_argument("--format", choices=("text", "json"), default="text")
    li.add_argument(
        "--no-baseline", action="store_true",
        help="report findings grandfathered in graftlint_baseline.json too",
    )
    li.add_argument(
        "--ir", action="store_true",
        help="run the IR tier: abstractly trace every registered kernel "
        "entry point on CPU and audit the jaxprs — run before a plane "
        "rollout (docs/OPERATIONS.md)",
    )
    li.add_argument(
        "--dep", action="store_true",
        help="run the dep tier: certify every kernel's row_coupled "
        "declaration against its jaxpr (delta-safety) and the "
        "replicated-scan discipline in sharded variants",
    )
    li.add_argument(
        "--all", dest="all_tiers", action="store_true",
        help="run AST + IR + dep tiers in one invocation (merged exit "
        "code, per-tier timing) — the CI/rollout gate shape",
    )
    li.add_argument(
        "--manifest", default=None, metavar="PATH",
        help="IR tier: also audit a prewarm trace manifest (every record "
        "must re-trace to its recorded signature)",
    )
    li.add_argument(
        "--changed-only", action="store_true",
        help="scope every tier to files with uncommitted git changes "
        "(the pre-commit mode, see docs/DEVELOPMENT.md)",
    )
    return parser, sub


def cmd_lint(
    paths: Sequence[str] = (), *, fmt: str = "text", baseline: bool = True,
    ir: bool = False, dep: bool = False, all_tiers: bool = False,
    manifest: str | None = None, changed_only: bool = False,
) -> int:
    """The ``lint`` verb: run the repo's static analyzer
    (tools/graftlint) over ``paths`` (default: the package + tools).
    Works from a checkout — the analyzer rides beside the package, not
    inside it (it is a development gate, not a serving component). The
    verb DELEGATES to graftlint's own CLI so output shape, exit codes and
    defaults can never drift between the two surfaces."""
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isdir(os.path.join(repo_root, "tools", "graftlint")):
        print(
            "error: graftlint not found — `lint` runs from a repo "
            "checkout (tools/graftlint/)",
            file=sys.stderr,
        )
        return 2
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from tools.graftlint.__main__ import main as graftlint_main

    argv = list(paths) + ["--root", repo_root, "--format", fmt]
    if not baseline:
        argv.append("--no-baseline")
    if ir:
        argv.append("--ir")
    if dep:
        argv.append("--dep")
    if all_tiers:
        argv.append("--all")
    if manifest is not None:
        argv += ["--manifest", manifest]
    if changed_only:
        argv.append("--changed-only")
    return graftlint_main(argv)


def cmd_trace_dump(
    metrics: str = "",
    wave: Optional[int] = None,
    summary: bool = False,
    stitch: bool = False,
    peers: str = "",
) -> dict:
    """The ``trace dump`` verb: the wave-trace ring + per-wave phase
    summaries, either from a remote process's ``/debug/traces`` endpoint
    (``metrics="host:port"``) or this process's in-proc tracer. The
    per-phase summary is the same shape the observability bench records
    (BENCH_OBS_r*.json), so a dumped wave reads against the docs table.

    ``stitch=True`` additionally pulls ``/debug/traces`` from every peer
    (``peers="name=host:port,..."`` wins, else the dumped process's own
    registered peers, else this process's registry incl.
    KARMADA_TPU_TRACE_PEERS) and merges the cross-process wave trees:
    remote handler roots re-parent under their originating client spans
    and per-process/per-channel self-time columns come out
    (utils.tracing.stitch_dumps)."""
    from .utils.tracing import (
        fetch_peer_dumps,
        register_peers_from_env,
        stitch_dumps,
        trace_debug_doc,
    )
    from .utils.tracing import peers as registered_peers

    if metrics:
        import urllib.request

        with urllib.request.urlopen(
            f"http://{metrics}/debug/traces", timeout=10
        ) as resp:
            doc = json.loads(resp.read().decode())
    else:
        doc = trace_debug_doc()
    if stitch:
        peer_map: dict = {}
        if peers:
            for part in peers.split(","):
                name, sep, addr = part.strip().partition("=")
                if sep and name and addr:
                    peer_map[name.strip()] = addr.strip()
        else:
            peer_map = dict(doc.get("peers") or {})
            if not peer_map:
                register_peers_from_env()
                peer_map = registered_peers()
        # never re-fetch the dumped process itself
        peer_map = {
            name: addr for name, addr in peer_map.items()
            if addr != metrics
        }
        doc = stitch_dumps(
            doc, fetch_peer_dumps(peer_map, wave=wave), wave=wave
        )
    if wave is not None:
        doc["spans"] = [s for s in doc["spans"] if s.get("wave") == wave]
        doc["waves"] = [w for w in doc["waves"] if w.get("wave") == wave]
    if summary:
        doc.pop("spans", None)
    return doc


def cmd_trace_analyze(path: str, wave: Optional[int] = None) -> dict:
    """The ``trace analyze`` verb: re-derive a flight-recorder record's
    attribution from its raw spans, offline. ``wave`` picks the record
    for that wave id (default: the newest record in the file); the
    result carries the recomputed summary, an ``identical`` flag proving
    the stitcher re-derives exactly what was recorded, and the rendered
    attribution table."""
    from .utils.tracing import analyze_record, load_flight_records

    records = load_flight_records(path)
    if not records:
        raise ValueError(f"{path}: no flight records")
    if wave is not None:
        matching = [r for r in records if r.get("wave") == wave]
        if not matching:
            raise ValueError(f"{path}: no flight record for wave {wave}")
        record = matching[-1]
    else:
        record = records[-1]
    return analyze_record(record)


def cmd_explain_placement(
    ref: str, wave: Optional[int] = None, metrics: str = ""
) -> dict:
    """The ``explain <ns>/<name>`` verb: one binding's placement
    decision chain from the provenance plane — either a remote
    process's ``/debug/explain`` endpoint (``metrics="host:port"``) or
    this process's in-proc ExplainStore. The answered document is THE
    ``/debug/explain?binding=`` shape, so the CLI, the HTTP surface and
    the flight recorder can never drift."""
    if metrics:
        import urllib.parse
        import urllib.request

        query = f"?binding={urllib.parse.quote(ref, safe='')}"
        if wave is not None:
            query += f"&wave={wave}"
        with urllib.request.urlopen(
            f"http://{metrics}/debug/explain{query}", timeout=10
        ) as resp:
            return json.loads(resp.read().decode())
    from .utils.explainstore import store as explain_store
    from .utils.tracing import tracer as _tracer

    return explain_store().debug_doc(
        binding=ref, wave=wave, proc=_tracer.proc
    )


#: the quota families `quota status` reads off the exposition — kept in
#: one place so the verb and its parser cannot drift
_QUOTA_FAMILIES = (
    "karmada_tpu_quota_limit",
    "karmada_tpu_quota_used",
    "karmada_tpu_quota_denied_total",
)


def _parse_exposition_lines(text: str, families) -> list:
    """(family, labels dict, value) rows for the requested families from
    Prometheus text exposition — enough of the format for the flat
    counter/gauge families the quota plane exports."""
    import re as _re

    out = []
    line_re = _re.compile(
        r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$"
    )
    label_re = _re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')
    wanted = set(families)
    # single-pass unescape: sequential str.replace corrupts values with
    # literal backslashes (an escaped \\ followed by n would collapse to
    # a newline)
    esc = {"\\\\": "\\", '\\"': '"', "\\n": "\n"}
    unescape = _re.compile(r'\\\\|\\"|\\n')
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        m = line_re.match(line.strip())
        if m is None or m.group("name") not in wanted:
            continue
        labels = {
            k: unescape.sub(lambda mm: esc[mm.group(0)], v)
            for k, v in label_re.findall(m.group("labels") or "")
        }
        try:
            value = float(m.group("value"))
        except ValueError:
            continue
        out.append((m.group("name"), labels, value))
    return out


def cmd_quota_status(metrics: str = "") -> dict:
    """The ``quota status`` verb: per-namespace limit/used/denied, read
    from a running process's /metrics endpoint (``metrics="host:port"``)
    or this process's in-proc registry. The families are the quota
    plane's exposition (FRQ status controller sets limit/used; the
    scheduler's denial path counts denied), so the verb needs no store
    access — any scrapable plane answers."""
    if metrics:
        import urllib.request

        with urllib.request.urlopen(
            f"http://{metrics}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        rows = _parse_exposition_lines(text, _QUOTA_FAMILIES)
    else:
        from .utils.metrics import registry as _registry

        rows = _parse_exposition_lines(
            _registry.render(), _QUOTA_FAMILIES
        )
    namespaces: dict = {}
    for family, labels, value in rows:
        ns = labels.get("namespace", "")
        entry = namespaces.setdefault(
            ns, {"resources": {}, "denied_total": 0}
        )
        if family == "karmada_tpu_quota_denied_total":
            entry["denied_total"] = int(value)
            continue
        res = labels.get("resource", "")
        slot = entry["resources"].setdefault(res, {"limit": 0, "used": 0})
        slot["limit" if family.endswith("_limit") else "used"] = int(value)
    return {"namespaces": namespaces}


def exposition_quantiles(
    text: str, family: str, qs
) -> dict[float, dict[tuple, float]]:
    """Bucket-interpolated quantiles straight off Prometheus text
    exposition (ISSUE 12 satellite): parse ``{family}_bucket`` /
    ``{family}_count`` rows ONCE with the SAME ``_parse_exposition_
    lines`` helper the quota-status verb uses, then estimate every
    requested quantile via the shared ``utils.metrics.bucket_quantile``
    core — one interpolation rule for the live Histogram and every CLI
    reading a scrape, so operators stop eyeballing raw cumulative
    buckets. Returns {q: {non-le label tuple: value}}."""
    from .utils.metrics import bucket_quantile

    rows = _parse_exposition_lines(
        text, (family + "_bucket", family + "_count")
    )
    buckets: dict[tuple, list] = {}
    totals: dict[tuple, int] = {}
    for name, labels, value in rows:
        if name.endswith("_count"):
            key = tuple(sorted(labels.items()))
            totals[key] = int(value)
            continue
        le = labels.get("le")
        if le is None or le.lstrip("+") == "Inf":
            continue
        key = tuple(sorted(
            (k, v) for k, v in labels.items() if k != "le"
        ))
        buckets.setdefault(key, []).append((float(le), int(value)))
    out: dict[float, dict[tuple, float]] = {q: {} for q in qs}
    for key, bs in buckets.items():
        bs.sort()
        bounds = [b for b, _ in bs]
        counts = [c for _, c in bs]
        total = totals.get(key, counts[-1] if counts else 0)
        for q in qs:
            v = bucket_quantile(q, bounds, counts, total)
            if v is not None:
                out[q][key] = v
    return out


def exposition_quantile(
    text: str, family: str, q: float
) -> dict[tuple, float]:
    """One-quantile form of ``exposition_quantiles`` (same parse, same
    interpolation)."""
    return exposition_quantiles(text, family, (q,))[q]


def cmd_plane_top(
    metrics: str = "", peers: str = "", window: int = 64
) -> dict:
    """The ``top`` verb: aggregate ``/debug/history`` (and the
    settle-latency histogram off ``/metrics``) across the plane's
    processes into one document — the target endpoint (or this
    process's in-proc history), plus every registered peer. Unreachable
    peers degrade to an ``error`` entry; the reachable plane still
    renders."""
    import urllib.request

    from .utils import tracing as trc
    from .utils.history import history_for

    def fetch(addr: str) -> tuple[dict, str]:
        with urllib.request.urlopen(
            f"http://{addr}/debug/history?window={window}", timeout=3
        ) as resp:
            doc = json.loads(resp.read().decode())
        try:
            with urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=3
            ) as resp:
                text = resp.read().decode()
        except Exception:  # noqa: BLE001 — digest-only degradation
            text = ""
        return doc, text

    peer_map: dict[str, str] = {}
    if peers:
        for part in peers.split(","):
            name, sep, addr = part.strip().partition("=")
            if sep and name.strip() and addr.strip():
                peer_map[name.strip()] = addr.strip()

    fetched: dict[str, tuple[dict, str]] = {}
    if metrics:
        doc, text = fetch(metrics)
        fetched[doc.get("proc") or "target"] = (doc, text)
        if not peer_map:
            peer_map = {
                n: a for n, a in (doc.get("peers") or {}).items()
                if a != metrics
            }
    else:
        from .utils.metrics import registry as _registry

        tr = trc.tracer
        doc = history_for(tr).debug_doc(window=window, proc=tr.proc)
        doc["peers"] = trc.peers()
        fetched[tr.proc] = (doc, _registry.render())
        if not peer_map:
            peer_map = trc.peers()
        if not peer_map:
            # parse the env WITHOUT registering: a read-only monitoring
            # verb must not flip the embedded plane's every later wave
            # close into stitched per-close sampling (peers() gates it)
            import os as _os

            raw = _os.environ.get("KARMADA_TPU_TRACE_PEERS", "")
            for part in raw.split(","):
                name, sep, addr = part.strip().partition("=")
                if sep and name.strip() and addr.strip():
                    peer_map[name.strip()] = addr.strip()

    # peers fetch CONCURRENTLY: N black-holed peers must cost one
    # timeout, not N serial ones (a --watch refresh blocks on this)
    todo = {
        name: addr for name, addr in sorted(peer_map.items())
        if name not in fetched
    }
    if todo:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=min(len(todo), 8)) as pool:
            futures = {
                name: pool.submit(fetch, addr)
                for name, addr in todo.items()
            }
        for name, fut in futures.items():
            try:
                fetched[name] = fut.result()
            except Exception as exc:  # noqa: BLE001 — peer down:
                # render the rest
                fetched[name] = (
                    {"error": f"{type(exc).__name__}: {exc}"}, ""
                )

    out: dict = {"window": window, "procs": {}}
    for name, (doc, text) in fetched.items():
        if "error" in doc:
            out["procs"][name] = {"error": doc["error"]}
            continue
        entry = {
            "cap": doc.get("cap"),
            "sampled": doc.get("sampled"),
            "evicted": doc.get("evicted"),
            "rows": doc.get("rows", []),
            "digests": doc.get("digests", {}),
        }
        if text:
            for fam, slot in (
                ("karmada_tpu_settle_seconds", "settle"),
                ("karmada_tpu_scheduler_pass_seconds", "pass"),
            ):
                by_q = exposition_quantiles(text, fam, (0.5, 0.95))
                p50 = by_q[0.5].get(())
                p95 = by_q[0.95].get(())
                if p50 is not None:
                    entry[f"{slot}_p50_s"] = round(p50, 6)
                if p95 is not None:
                    entry[f"{slot}_p95_s"] = round(p95, 6)
            # ISSUE 13 satellite: the per-process device-byte total the
            # PR 12 ledger publishes (summed over {kind,bucket}) and the
            # unschedulable/denied totals off the new reason family —
            # the history rows carry per-wave deltas; these are the
            # process-lifetime levels the aggregate used to drop
            levels = _parse_exposition_lines(
                text,
                (
                    "karmada_tpu_device_bytes",
                    "karmada_tpu_unschedulable_total",
                    "karmada_tpu_quota_denied_total",
                    "karmada_tpu_preemptions_total",
                    "karmada_tpu_desched_disruption_budget",
                    "karmada_tpu_desched_disruption_used",
                ),
            )
            totals = {"karmada_tpu_device_bytes": 0.0,
                      "karmada_tpu_unschedulable_total": 0.0,
                      "karmada_tpu_quota_denied_total": 0.0,
                      "karmada_tpu_preemptions_total": 0.0,
                      "karmada_tpu_desched_disruption_budget": 0.0,
                      "karmada_tpu_desched_disruption_used": 0.0}
            by_reason: dict = {}
            preempt_by_reason: dict = {}
            for fam, labels, value in levels:
                totals[fam] += value
                if fam == "karmada_tpu_unschedulable_total":
                    reason = labels.get("reason", "")
                    by_reason[reason] = (
                        by_reason.get(reason, 0) + int(value)
                    )
                elif fam == "karmada_tpu_preemptions_total":
                    reason = labels.get("reason", "")
                    preempt_by_reason[reason] = (
                        preempt_by_reason.get(reason, 0) + int(value)
                    )
            entry["device_bytes"] = int(
                totals["karmada_tpu_device_bytes"]
            )
            entry["unschedulable_total"] = int(
                totals["karmada_tpu_unschedulable_total"]
            )
            entry["quota_denied_total"] = int(
                totals["karmada_tpu_quota_denied_total"]
            )
            # ISSUE 14 satellite: the scarcity-plane levels — lifetime
            # preemptions (by reason) plus the descheduler's live
            # disruption budget/used pair
            entry["preemptions_total"] = int(
                totals["karmada_tpu_preemptions_total"]
            )
            entry["disruption_budget"] = int(
                totals["karmada_tpu_desched_disruption_budget"]
            )
            entry["disruption_used"] = int(
                totals["karmada_tpu_desched_disruption_used"]
            )
            if by_reason:
                entry["unschedulable_by_reason"] = dict(
                    sorted(by_reason.items())
                )
            if preempt_by_reason:
                entry["preemptions_by_reason"] = dict(
                    sorted(preempt_by_reason.items())
                )
        out["procs"][name] = entry
    return out


def render_top(doc: dict) -> str:
    """The ``top`` table: the latest wave row per process, then the
    recent-window digests (p50/p95 per headline series) and the live
    settle quantiles."""
    from .utils.history import render_history_table

    latest = []
    for name, entry in sorted(doc.get("procs", {}).items()):
        for row in entry.get("rows", [])[-1:]:
            row = dict(row)
            row["proc"] = name
            latest.append(row)
    lines = [render_history_table(latest)] if latest else [
        "(no history rows sampled yet)"
    ]
    for name, entry in sorted(doc.get("procs", {}).items()):
        if "error" in entry:
            lines.append(f"{name}: unreachable ({entry['error']})")
            continue
        series = (entry.get("digests") or {}).get("series", {})
        window = (entry.get("digests") or {}).get("window", 0)
        bits = []
        for key, label in (
            ("wall_s", "wall"),
            ("bindings_s", "bind/s"),
            ("coverage", "cover"),
            ("device_bytes", "devB"),
        ):
            d = series.get(key)
            if d:
                bits.append(
                    f"{label} p50 {d['p50']:.3g} p95 {d['p95']:.3g}"
                )
        for slot in ("settle", "pass"):
            if f"{slot}_p50_s" in entry:
                bits.append(
                    f"{slot} p50 {entry[f'{slot}_p50_s']:.3g}s "
                    f"p95 {entry.get(f'{slot}_p95_s', 0.0):.3g}s"
                )
        if "device_bytes" in entry:
            bits.append(f"devB {entry['device_bytes'] / 1e6:.2f}MB")
        if entry.get("unschedulable_total") or entry.get(
            "quota_denied_total"
        ):
            bits.append(
                f"unsched/denied {entry.get('unschedulable_total', 0)}"
                f"/{entry.get('quota_denied_total', 0)}"
            )
        if entry.get("preemptions_total"):
            bits.append(f"preempted {entry['preemptions_total']}")
        if entry.get("disruption_budget"):
            bits.append(
                f"disruption {entry.get('disruption_used', 0)}"
                f"/{entry['disruption_budget']}"
            )
        if entry.get("evicted"):
            bits.append(f"evicted {entry['evicted']}")
        if bits:
            lines.append(
                f"{name} (last {window} wave(s)): " + ", ".join(bits)
            )
    return "\n".join(lines)


def cmd_warmup(manifest: str = "", expand: bool = True) -> dict:
    """The ``warmup`` verb: replay the trace manifest through AOT
    compilation on the current backend (scheduler.prewarm.warmup), so a
    following plane/solver boot — or this process's first schedule pass —
    pays zero compile cost for covered fleet shapes."""
    from .scheduler.prewarm import warmup

    return warmup(manifest or None, expand=expand)


def lint_main(argv: Optional[list[str]] = None) -> int:
    """Console entry for the ``karmada-tpu-lint`` convenience script
    (pyproject [project.scripts]): ``karmada-tpu-lint --changed-only`` is
    the pre-commit hook body, ``karmada-tpu-lint --ir`` the pre-rollout
    audit — both delegate through the ``lint`` verb so the script, the
    verb and ``python -m tools.graftlint`` cannot drift."""
    if argv is None:
        argv = sys.argv[1:]
    return main(["lint", *argv])


def main(argv: Optional[list[str]] = None) -> int:
    """argparse front end. With ``--bus`` (and optionally ``--proxy``) the
    commands operate on a REMOTE plane over the wire — state through the
    store bus, member access through the cluster proxy; without it,
    ``local-up`` bootstraps a demo plane in-process (``--processes`` spawns
    the full multi-process deployment instead)."""
    parser, _sub = build_parser()
    args = parser.parse_args(argv)

    # offline verbs: no plane, no bus
    if args.command == "explain":
        if "/" in args.path:
            # <ns>/<name>: the provenance plane's decision chain
            try:
                doc = cmd_explain_placement(
                    args.path, wave=args.wave, metrics=args.metrics
                )
            except Exception as exc:  # unreachable endpoint, bad JSON
                print(json.dumps({"error": str(exc)}))
                return 1
            if args.as_json:
                print(json.dumps(doc, indent=2))
            else:
                from .utils.explainstore import render_explanation

                print(render_explanation(doc.get("binding")))
            return 0 if doc.get("binding") is not None else 1
        try:
            print(cmd_explain(args.path))
        except KeyError as exc:
            print(json.dumps({"error": str(exc.args[0])}))
            return 1
        return 0
    if args.command == "completion":
        print(cmd_completion(args.shell))
        return 0
    if args.command == "lint":
        return cmd_lint(
            args.paths, fmt=args.format, baseline=not args.no_baseline,
            ir=args.ir, dep=args.dep, all_tiers=args.all_tiers,
            manifest=args.manifest, changed_only=args.changed_only,
        )
    if args.command == "trace":
        if args.action == "analyze":
            if not args.record:
                print(json.dumps(
                    {"error": "trace analyze needs a record path"}
                ))
                return 1
            try:
                doc = cmd_trace_analyze(args.record, wave=args.wave)
            except Exception as exc:  # missing/corrupt record file
                print(json.dumps({"error": str(exc)}))
                return 1
            table = doc.pop("table", "")
            print(json.dumps(doc, indent=2))
            if table:
                print(table, file=sys.stderr)
            return 0
        try:
            doc = cmd_trace_dump(
                args.metrics, wave=args.wave, summary=args.summary,
                stitch=args.stitch, peers=args.peers,
            )
        except Exception as exc:  # unreachable endpoint, bad JSON
            print(json.dumps({"error": str(exc)}))
            return 1
        print(json.dumps(doc, indent=2))
        return 0
    if args.command == "quota":
        try:
            doc = cmd_quota_status(args.metrics)
        except Exception as exc:  # unreachable endpoint, bad text
            print(json.dumps({"error": str(exc)}))
            return 1
        print(json.dumps(doc, indent=2))
        return 0
    if args.command == "top":
        import time as _time

        while True:
            try:
                doc = cmd_plane_top(
                    args.metrics, peers=args.peers, window=args.window
                )
            except KeyboardInterrupt:
                # Ctrl-C mid-fetch in --watch mode is a clean exit,
                # not a traceback
                return 0
            except Exception as exc:  # unreachable target endpoint
                print(json.dumps({"error": str(exc)}))
                if not args.watch:
                    return 1
                # a watch survives one failed scrape (target restarting)
                # and retries on the next interval
                doc = None
            if doc is not None:
                if args.as_json:
                    print(json.dumps(doc, indent=2))
                else:
                    print(render_top(doc))
            if not args.watch:
                return 0
            try:
                _time.sleep(args.interval)
            except KeyboardInterrupt:
                return 0
            print()  # blank separator between refreshes
    if args.command == "warmup":
        stats = cmd_warmup(args.manifest, expand=not args.no_expand)
        print(json.dumps(stats))
        # no manifest yet is a no-op boot optimization, not a failure;
        # per-record compile failures (stale manifest vs new build) are
        # reported in the JSON but only a total wipe-out exits nonzero
        return 1 if (stats["failed"] and not stats["compiled"]) else 0

    if args.command == "local-up":
        if args.processes:
            from .localup import LocalUp

            with LocalUp(members=args.members) as lup:
                print(json.dumps(lup.endpoints), flush=True)
                try:
                    while all(p.poll() is None for p in lup.procs.values()):
                        import time as _t

                        _t.sleep(1)
                except KeyboardInterrupt:
                    pass
            return 0
        cp = cmd_local_up(args.members)
        clusters = [c.name for c in cp.store.list("Cluster")]
        print(json.dumps({"clusters": clusters}))
        return 0

    if not args.bus:
        print("error: this command needs --bus HOST:PORT", file=sys.stderr)
        return 2
    from .utils.codec import to_jsonable

    with RemotePlane(args.bus, args.proxy, token=args.token) as rp:
        if args.command == "get":
            labels = {}
            if args.selector:
                if args.name:
                    # kubectl rejects the combination outright: a selector
                    # on a NAMED get is never applied by any backend
                    print(json.dumps({
                        "error": "--selector and --name are mutually "
                        "exclusive (kubectl semantics)"
                    }))
                    return 2
                for part in args.selector.split(","):
                    k, sep, v = part.partition("=")
                    if not sep:
                        print(json.dumps(
                            {"error": f"bad selector segment {part!r}"}
                        ))
                        return 2
                    labels[k.strip()] = v.strip()
            resp = cmd_get(
                rp, args.gvk, args.namespace, args.name,
                cluster=args.cluster or None, labels=labels or None,
            )
            if resp.error:
                print(json.dumps({"error": resp.error}))
                return 1
            doc = (
                to_jsonable(resp.obj)
                if resp.obj is not None
                else [
                    {"cluster": c, "object": to_jsonable(o)}
                    for c, o in resp.items
                ]
            )
            print(_format_get(doc, args.output, args.gvk))
        elif args.command == "describe":
            print(cmd_describe(rp, args.gvk, args.namespace, args.name))
        elif args.command == "logs":
            for line in cmd_logs(
                rp, args.cluster, args.namespace, args.pod, tail=args.tail
            ):
                print(line)
        elif args.command == "cordon":
            cmd_cordon(rp, args.name)
            print(f"cluster/{args.name} cordoned")
        elif args.command == "uncordon":
            cmd_uncordon(rp, args.name)
            print(f"cluster/{args.name} uncordoned")
        elif args.command == "taint":
            cmd_taint(
                rp, args.name, args.key, args.value, args.effect,
                remove=args.remove,
            )
            print(f"cluster/{args.name} tainted")
        elif args.command == "promote":
            cmd_promote(rp, args.cluster, args.gvk, args.namespace, args.name)
            print(f"{args.gvk} {args.namespace}/{args.name} promoted")
        elif args.command in ("apply", "create"):
            fn = cmd_apply if args.command == "apply" else cmd_create
            try:
                if args.filename == "-":
                    text = sys.stdin.read()
                else:
                    with open(args.filename) as f:
                        text = f.read()
                applied = fn(rp, _load_manifests(text))
            except Exception as exc:  # unreadable file, parse, admission
                print(json.dumps({"error": str(exc)}))
                return 1
            verb = "created" if args.command == "create" else "applied"
            for ref in applied:
                print(f"{ref} {verb}")
        elif args.command == "delete":
            ok = cmd_delete(
                rp, args.kind, args.namespace, args.name, force=args.force
            )
            if not ok:
                print(json.dumps({"error": "not found"}))
                return 1
            print(f"{args.kind}/{args.namespace}/{args.name} deleted")
        elif args.command == "patch":
            try:
                obj = cmd_patch(
                    rp, args.kind, args.namespace, args.name,
                    json.loads(args.patch), args.patch_type,
                )
            except Exception as exc:
                print(json.dumps({"error": str(exc)}))
                return 1
            print(json.dumps(to_jsonable(obj)))
        elif args.command == "edit":
            try:
                obj = cmd_edit(
                    rp, args.kind, args.namespace, args.name,
                    editor=args.editor,
                )
            except Exception as exc:
                print(json.dumps({"error": str(exc)}))
                return 1
            if obj is None:
                print("Edit cancelled, no changes made.")
            else:
                print(json.dumps(to_jsonable(obj)))
        elif args.command in ("label", "annotate"):
            fn = cmd_label if args.command == "label" else cmd_annotate
            try:
                obj = fn(rp, args.kind, args.namespace, args.name, args.changes)
            except Exception as exc:
                print(json.dumps({"error": str(exc)}))
                return 1
            print(json.dumps(to_jsonable(obj)))
        elif args.command == "api-resources":
            print(json.dumps(cmd_api_resources(rp)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
