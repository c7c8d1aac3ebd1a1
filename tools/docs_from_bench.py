"""Regenerate the measured-numbers blocks in the docs from a bench record.

Usage: python tools/docs_from_bench.py BENCH_SELF_r05.json
       python tools/docs_from_bench.py --env-table

Rewrites the text between ``<!-- bench:begin -->`` / ``<!-- bench:end -->``
markers in docs/OPERATIONS.md and BASELINE.md from the JSON line bench.py
printed (either the raw line or the driver's ``{"parsed": ...}`` wrapper).
Round 4 shipped docs claiming ~10 s where the recorded JSON said 71.6 s;
with this tool the prose can never drift from the record again —
regenerate, don't hand-edit.

The same contract covers the environment-variable table: the block between
``<!-- envflags:begin -->`` / ``<!-- envflags:end -->`` in
docs/OPERATIONS.md is generated from ``karmada_tpu.utils.flags.ENV_FLAGS``
(``--env-table`` rewrites it), and EVERY doc-regeneration run fails loudly
when the committed table has drifted from the registry — the docs half of
graftlint's GL003 gate.

Same drift-guard pattern for the kernel audit surface: every regeneration
run also fails loudly when a kernel family exported from
``karmada_tpu/ops/`` is missing from the graftlint IR entry-point registry
(``tools/graftlint/ir.py`` ENTRY_POINTS) — a kernel the IR tier cannot see
is a kernel whose dtype/transfer/capture invariants nothing proves.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def fmt(v, unit="s") -> str:
    return "n/a" if v is None else f"{v:.2f} {unit}"


def block(d: dict) -> str:
    tiers = d.get("tiers", {})
    bad = {k: v for k, v in tiers.items() if v != "ok"}
    lines = [
        "| tier | measured |",
        "|---|---|",
        f"| 100k×5k steady storm p50 | {fmt(d.get('value'))} "
        f"({d.get('vs_cpp_native', 0):.0f}× the calibrated C++ -O2 "
        f"referent, {d.get('vs_numpy_host', 0):.0f}× vectorized numpy) |",
        f"| 100k×5k full-drift churn p50 / max | {fmt(d.get('churn_p50'))}"
        f" / {fmt(d.get('churn_max'))} |",
        f"| hetero 3500 uniques steady p50 | {fmt(d.get('hetero3500_p50'))} |",
        f"| hetero 9000 uniques steady p50 | {fmt(d.get('hetero9000_p50'))} |",
        f"| hetero 9000 slot-eviction churn p50 (10% unique rotation/pass) |"
        f" {fmt(d.get('hetero9k_churn_p50'))} |",
        f"| live-gRPC estimator tier (512 clusters, 4 server processes) "
        f"storm p50 | {fmt(d.get('estimator512_p50'))} (refresh "
        f"{fmt(d.get('estimator512_refresh_p50'))}, placements "
        + {True: "identical", False: "DIVERGED", None: "n/a"}[
            d.get("estimator512_identical")
        ]
        + " vs snapshot-fed) |",
        f"| 1M×5k steady p50 | {fmt(d.get('scale1m_steady_p50'))} |",
        f"| 1M×5k full-drift churn p50 / max | "
        f"{fmt(d.get('scale1m_churn_p50'))} / "
        f"{fmt(d.get('scale1m_churn_max'))} |",
    ]
    wp = d.get("whole_plane_bindings_s")
    if wp is not None:
        lines.append(
            f"| whole-plane storm (detector→scheduler→binding→works) | "
            f"{wp:,.0f} bindings/s |"
        )
    lines.append(
        f"| verification | {d.get('verified_rows', 0):,} oracle-verified "
        f"rows, {d.get('verified_mismatches', 0)} mismatches |"
    )
    if bad:
        lines.append(f"| FAILED tiers this run | {sorted(bad)} |")
    return "\n".join(lines)


def cold_block(cd: dict) -> str:
    """Rows for a ``bench.py --cold-start`` record (the plane-restart
    first-wave tier): cold = cache+manifest disabled, restore = manifest
    prewarm + persistent compile cache."""
    scale = cd.get("metric", "").removeprefix("cold_start_first_wave_")
    warm = cd.get("restore_new_trace_first_pass")
    return "\n".join(
        [
            f"| cold-start {scale}: first wave, no cache (pre-subsystem "
            f"restart) | {fmt(cd.get('cold_first_wave_s'))} "
            f"({cd.get('cold_over_warm', 0):.1f}× the warm all-change "
            f"wave) |",
            f"| cold-start {scale}: first wave, cached + manifest-prewarmed "
            f"restart | {fmt(cd.get('restore_first_wave_s'))} "
            f"({cd.get('restore_over_warm', 0):.2f}× warm, "
            f"{cd.get('vs_baseline', 0):.1f}× faster than cold, first pass "
            f"new_trace={'False' if warm is False else warm}) |",
        ]
    )


def estimator_block(ed: dict) -> str:
    """Rows for a ``bench.py --estimator-only`` record (the batched
    estimator wire tier): full-refresh storm over one batch RPC per
    server, generation-ping no-movement refresh, and the unary-fallback
    parity run with its width-1 (blocking sequential) reference."""
    scale = ed.get("metric", "").removeprefix("estimator512_wire_")

    def rpcs(key):
        d = ed.get(key) or {}
        parts = [
            f"{d.get(k, 0)} {k}" for k in ("batch", "unary", "ping")
            if d.get(k)
        ]
        return " + ".join(parts) if parts else "0"

    ident = {True: "identical", False: "DIVERGED", None: "n/a"}
    return "\n".join(
        [
            f"| estimator wire {scale}: full-refresh storm p50 (batched "
            f"protocol) | {fmt(ed.get('estimator512_p50'))} (RPCs/pass: "
            f"{rpcs('estimator512_rpc_full')}; placements "
            f"{ident[ed.get('estimator512_identical')]} vs snapshot-fed) |",
            f"| estimator wire {scale}: no-movement refresh pass "
            f"(generation pings only) | "
            f"{fmt(ed.get('estimator512_refresh_p50'))} (RPCs/pass: "
            f"{rpcs('estimator512_rpc_steady')}) |",
            f"| estimator wire {scale}: unary-fallback full refresh "
            f"(mixed-version path, pipelined) | "
            f"{fmt(ed.get('estimator512_fallback_p50'))} (RPCs/pass: "
            f"{rpcs('estimator512_rpc_fallback')}; placements "
            f"{ident[ed.get('estimator512_fallback_identical')]}; "
            f"blocking-sequential reference "
            f"{fmt(ed.get('estimator512_fallback_seq_s'))}) |",
        ]
    )


def obs_block(od: dict) -> str:
    """Rows for a ``bench.py --observability`` record (the wave-trace
    attribution tier): coverage of the measured wall clock, the kernel
    compile/device/host split, and the heaviest wave phases."""
    scale = od.get("metric", "").removeprefix("observability_wave_")
    cov = od.get("coverage_vs_wall", 0.0)
    phases = od.get("phases", {}) or {}
    top = sorted(phases.items(), key=lambda kv: -kv[1])[:5]
    top_s = ", ".join(f"{k} {v:.2f}s" for k, v in top)
    compiles = od.get("kernel_compiles", {}) or {}
    comp_s = (
        ", ".join(f"{k} x{int(v)}" for k, v in sorted(compiles.items()))
        or "none"
    )
    rows = [
        f"| observability {scale}: storm wave wall / span coverage | "
        f"{fmt(od.get('value'))} wall, {cov * 100:.1f}% attributed to "
        f"named spans ({od.get('bindings_s', 0):,.0f} bindings/s, "
        f"{od.get('works', 0):,} works) |",
        f"| observability {scale}: kernel span split | "
        f"host(pack/decode) {phases.get('kernel.host', 0.0):.2f}s, "
        f"dispatch {phases.get('kernel.dispatch', 0.0):.2f}s (sync "
        f"backends execute inside it), device-fence "
        f"{phases.get('kernel.device', 0.0):.2f}s, fetch "
        f"{phases.get('kernel.fetch', 0.0):.2f}s; compile-bearing "
        f"{od.get('compile_s', 0.0):.2f}s |",
        f"| observability {scale}: heaviest wave phases (self time) | "
        f"{top_s} |",
        f"| observability {scale}: serving-path kernel compiles "
        f"(whole run) | {comp_s} |",
    ]
    # ISSUE 12: device-byte ledger columns + the history-backed wave
    # table summary
    dev = od.get("device_bytes") or {}
    if dev:
        dev_s = ", ".join(
            f"{k} {v / 1e6:.2f} MB" for k, v in sorted(dev.items())
        )
        const = {True: "constant", False: "MOVED"}[
            bool(od.get("device_bytes_steady_constant"))
        ]
        rows.append(
            f"| observability {scale}: resident device bytes "
            f"({od.get('device_bytes_platform', '?')} buffers; exact "
            f"nbytes of the held arrays) | {dev_s} — total "
            f"{od.get('device_bytes_total', 0) / 1e6:.2f} MB, {const} "
            f"across steady passes, gauge-ledger sum matches="
            f"{bool(od.get('device_bytes_matches_gauge'))} |"
        )
    hist = od.get("history_digests") or {}
    if hist:
        bits = []
        for key, label in (
            ("wall_s", "wall"),
            ("bindings_s", "bindings/s"),
            ("rows_packed", "rows packed"),
            ("rows_replayed", "rows replayed"),
        ):
            d = hist.get(key)
            if d:
                bits.append(
                    f"{label} p50 {d['p50']:g} / p95 {d['p95']:g}"
                )
        rows.append(
            f"| observability {scale}: per-wave history ring "
            f"({od.get('history_waves', 0)} waves sampled) | "
            f"{'; '.join(bits) or 'n/a'} |"
        )
    # ISSUE 10: the 4-process stitched wave (plane + solver sidecar +
    # estimator server + bus) with per-process and per-channel columns,
    # and the flight-recorder proof
    st = od.get("stitched")
    if st:
        proc_s = ", ".join(
            f"{k} {v:.2f}s"
            for k, v in sorted(
                (st.get("process_s") or {}).items(), key=lambda kv: -kv[1]
            )
        )
        chan_s = "; ".join(
            f"{k}: {v.get('rpcs', 0)} rpcs ({v.get('events_per_rpc', 1.0):g}"
            f" ev/msg), client {v.get('client_s', 0.0):.2f}s"
            f" = server {v.get('server_s', 0.0):.2f}s + network "
            f"{v.get('network_s', 0.0):.2f}s"
            for k, v in sorted((st.get("channels") or {}).items())
        )
        rows += [
            f"| observability {scale}: stitched 4-process wave "
            f"({', '.join(st.get('procs', []))}) | "
            f"{fmt(od.get('stitched_wall_s'))} wall, "
            f"{od.get('stitched_coverage_vs_wall', 0.0) * 100:.1f}% "
            f"attributed across processes ({st.get('spans', 0)} spans) |",
            f"| observability {scale}: per-process self time | "
            f"{proc_s or 'n/a'} |",
            f"| observability {scale}: per-channel columns "
            f"(client = server + network/serialization) | "
            f"{chan_s or 'n/a'} |",
            f"| observability {scale}: flight recorder (seeded breaker "
            f"trip mid-wave) | record written="
            f"{bool(od.get('flight_recorded'))}, reasons "
            f"{od.get('flight_reasons', [])}, `trace analyze` re-derives "
            f"identically={od.get('flight_analyze_identical')} |",
        ]
    # ISSUE 13: the provenance-plane rows — armed-vs-disarmed storm
    # overhead (benchguard-guarded), capture sizes, and the live
    # denied-binding + flight-record "why" proofs
    if od.get("explain_overhead_x") is not None:
        resolved = {True: "resolved", False: "UNRESOLVED"}[
            bool(od.get("explain_resolved"))
        ]
        flight = {True: "identical", False: "DIVERGED", None: "n/a"}[
            od.get("explain_flight_identical")
        ]
        rows += [
            f"| explain {scale}: armed vs disarmed storm wave | "
            f"{fmt(od.get('explain_armed_wave_s'))} armed vs "
            f"{fmt(od.get('explain_disarmed_wave_s'))} disarmed — "
            f"{od.get('explain_overhead_x', 0):.3f}x (within the "
            f"benchguard noise band; disarmed = one `is None` check) |",
            f"| explain {scale}: capture sizes | "
            f"{od.get('explain_capture_bindings', 0):,} bindings over "
            f"{od.get('explain_captures', 0)} capture(s), "
            f"{od.get('explain_capture_bytes', 0) / 1e6:.2f} MB interned "
            f"({od.get('explain_unique_masks', 0)} unique mask rows) |",
            f"| explain {scale}: decision chains | live denied binding "
            f"{resolved} via `karmadactl-tpu explain` "
            f"(stage={od.get('explain_denied_stage', '?')}); flight "
            f"record carries worst-binding explanations, `trace "
            f"analyze` re-renders {flight} |",
        ]
    # ISSUE 11: the columnar bus channel rows — storm throughput over
    # the live 4-process bus, the unary re-run ratio, the top stitched
    # self-time phase (bus.rpc must no longer lead), and the batched↔
    # unary plane-state parity verdict
    if od.get("bus_parity_identical") is not None:
        parity = {True: "IDENTICAL", False: "DIVERGED"}[
            bool(od.get("bus_parity_identical"))
        ]
        n_st = od.get("stitched_bindings", 0)
        rows += [
            f"| bus channel {n_st}x{od.get('stitched_clusters', 0)} "
            f"(4-process storm): batched vs unary wall | "
            f"{fmt(od.get('stitched_wall_s'))} batched "
            f"({od.get('stitched_bindings_s', 0):,.0f} bindings/s) vs "
            f"{fmt(od.get('bus_unary_wall_s'))} unary write path — "
            f"{od.get('bus_unary_vs_batched', 0):g}x |",
            f"| bus channel: top stitched self-time phase | "
            f"{od.get('bus_top_self_phase', '?')} "
            f"{od.get('bus_top_self_phase_s', 0.0):.2f}s |",
            f"| bus channel: template-delta rendering | "
            f"{od.get('bus_template_delta_works', 0):,} delta Works over "
            f"{od.get('bus_templates', 0):,} content-addressed templates |",
            f"| bus channel: plane state batched vs unary "
            f"(placements + rehydrated manifests) | {parity} |",
        ]
    return "\n".join(rows)


def chaos_block(cd: dict) -> str:
    """Rows for a ``bench.py --chaos`` record (the chaos-failover tier):
    time-to-stable-placement after the seeded kill wave, the displaced-
    binding count against the batched-solve count, the oracle-parity
    flag, and the breaker's degraded/recovery story."""
    scale = cd.get("metric", "").removeprefix("chaos_storm_")
    parity = {True: "IDENTICAL", False: "DIVERGED"}[
        bool(cd.get("oracle_identical"))
    ]
    degraded = cd.get("degraded_storm_s") or []
    degraded_s = ", ".join(f"{s:.1f}s" for s in degraded) or "n/a"
    return "\n".join(
        [
            f"| chaos {scale}: kill {len(cd.get('killed_clusters', []))} "
            f"clusters + partition 1 estimator server mid-wave → stable "
            f"placement | {fmt(cd.get('time_to_stable_s'))} "
            f"(steady storm p50 disarmed "
            f"{fmt(cd.get('steady_p50_disarmed_s'))}) |",
            f"| chaos {scale}: displaced bindings / batched solves | "
            f"{cd.get('displaced_bindings', 0):,} displaced rescheduled "
            f"in {cd.get('solves_failover_wave', 0)} batched solve(s) — "
            f"ordered ClusterAffinities fallback as one tensorized pass, "
            f"not per-binding Python |",
            f"| chaos {scale}: oracle parity (numpy per-binding replay of "
            f"the seeded event log, seed {cd.get('chaos_seed')}) | "
            f"{parity} ({cd.get('oracle_mismatches', 0)} mismatches, "
            f"{cd.get('replay_events', 0)} logged fault events) |",
            f"| chaos {scale}: estimator channel degraded mode | breaker "
            f"open observed={cd.get('breaker_open_observed')}, degraded "
            f"storms {degraded_s}, "
            f"{cd.get('degraded_estimator_passes', 0)} degraded passes "
            f"(never replay-armed), recovered half-open→closed without "
            f"operator action={cd.get('breaker_recovered_closed')} |",
        ]
    )


def quota_block(qd: dict) -> str:
    """Rows for a ``bench.py --quota`` record (the quota-enforcement
    tier): the CronFederatedHPA surge against tightened namespace quotas,
    the oracle-parity flags for admission AND placements, the
    enforcement-overhead bound against quota-disabled storms, and the
    raise-without-re-pack proof."""
    scale = qd.get("metric", "").removeprefix("quota_surge_")
    adm = {True: "IDENTICAL", False: "DIVERGED"}[
        bool(qd.get("admission_identical"))
    ]
    plc = {True: "IDENTICAL", False: "DIVERGED"}[
        bool(qd.get("placements_identical"))
    ]
    return "\n".join(
        [
            f"| quota {scale}: CronFederatedHPA surge "
            f"({qd.get('surged_bindings', 0):,} bindings rescaling into "
            f"{qd.get('quota_namespaces', 0)} quota'd namespaces, "
            f"{qd.get('capped_namespaces', 0)} with static caps) | "
            f"{fmt(qd.get('surge_wave_s'))} wave, "
            f"{qd.get('surge_solves', 0)} batched solve(s) — "
            f"{qd.get('scaled_bindings', 0):,} scaled, "
            f"{qd.get('denied_bindings', 0):,} denied QuotaExceeded |",
            f"| quota {scale}: oracle parity (sequential numpy admission "
            f"+ per-pass divider replay) | admission {adm} "
            f"({qd.get('admission_checked', 0):,} decisions), placements "
            f"{plc} ({qd.get('placements_checked', 0):,} rows) |",
            f"| quota {scale}: enforcement overhead on steady storms | "
            f"wall enforced {fmt(qd.get('steady_p50_enforced_s'))} vs "
            f"disabled {fmt(qd.get('steady_p50_disabled_s'))} "
            f"({qd.get('enforcement_overhead_x', 0):.3f}×); engine "
            f"schedule {fmt(qd.get('steady_sched_enforced_s'))} vs "
            f"{fmt(qd.get('steady_sched_disabled_s'))} "
            f"({qd.get('sched_overhead_x', 0):.3f}×) |",
            f"| quota {scale}: quota raise clears denials without a "
            f"re-pack | namespace {qd.get('raise_namespace')}: cleared "
            f"all={qd.get('raise_cleared_all')} in "
            f"{qd.get('raise_solves')} batched solve(s) |",
        ]
    )


def preempt_block(pd: dict) -> str:
    """Rows for a ``bench.py --preemption`` record (the scarcity tier):
    the high-priority surge against an exactly-saturated fleet with the
    victim/placement oracle-parity flags, the batched-solve shape, the
    armed-vs-disarmed steady-storm bound, and the bounded-disruption
    drift round."""
    scale = pd.get("metric", "").removeprefix("preempt_storm_")
    vic = {True: "IDENTICAL", False: "DIVERGED"}[
        bool(pd.get("victims_identical"))
    ]
    plc = {True: "IDENTICAL", False: "DIVERGED"}[
        bool(pd.get("placements_identical"))
    ]
    return "\n".join(
        [
            f"| preempt {scale}: high-priority surge on a saturated "
            f"fleet ({pd.get('surged_bindings', 0):,} priority-100 "
            f"bindings, zero free capacity) | "
            f"{fmt(pd.get('surge_wave_s'))} to stable, "
            f"{pd.get('victims_evicted', 0):,} victims evicted in "
            f"{pd.get('preemption_passes', 0)} preemption pass(es), "
            f"{pd.get('surge_solves', 0)} batched solves over "
            f"{pd.get('surge_engine_passes', 0)} engine passes |",
            f"| preempt {scale}: oracle parity (sequential numpy victim "
            f"selection + boosted per-binding divides) | victims {vic} "
            f"({pd.get('victims_checked', 0):,} rows), demander "
            f"placements {plc} ({pd.get('placements_checked', 0):,} "
            f"rows) |",
            f"| preempt {scale}: arming overhead on steady storms | "
            f"wall armed {fmt(pd.get('steady_p50_armed_s'))} vs "
            f"disarmed {fmt(pd.get('steady_p50_disarmed_s'))}; engine "
            f"schedule {fmt(pd.get('steady_sched_armed_s'))} vs "
            f"{fmt(pd.get('steady_sched_disarmed_s'))} "
            f"({pd.get('preempt_overhead_x', 0):.3f}×) |",
            f"| preempt {scale}: continuous-descheduler drift round | "
            f"{pd.get('drift_drifted', 0):,} of "
            f"{pd.get('drift_scored', 0):,} residents drifted; "
            f"{pd.get('drift_triggered', 0)}/{pd.get('drift_budget', 0)} "
            f"triggered (budget exact={pd.get('drift_budget_exact')}, "
            f"oracle identical={pd.get('drift_oracle_identical')}), "
            f"{pd.get('drift_replaced', 0)} re-placed in "
            f"{fmt(pd.get('drift_round_s'))} |",
        ]
    )


def multichip_block(md: dict) -> str:
    """Rows for a ``bench.py --multichip`` record (the sharded-engine
    tier): per-mesh steady p50 with the placement-identity flags, the
    donation (buffer-reuse) proof, and the steady-pass transfer bound
    against the full packed-grid upload."""
    scale = md.get("metric", "").removeprefix("multichip_scaling_")
    sizes = [str(s) for s in md.get("mesh_sizes", [])]
    p50 = md.get("steady_p50_s", {}) or {}
    ident = md.get("identical", {}) or {}
    don = md.get("donated", {}) or {}
    up = md.get("steady_upload_mb", {}) or {}
    curve = ", ".join(f"mesh {m}: {p50.get(m, 0.0):.2f}s" for m in sizes)
    ident_ok = all(ident.get(m) for m in sizes)
    don_ok = all(don.get(m) for m in sizes)
    max_up = max((up.get(m, 0.0) for m in sizes), default=0.0)
    full = md.get("full_grid_upload_mb", 0.0) or 0.0
    cpu_rig = md.get("platform") == "cpu"
    dev_kind = "forced host" if cpu_rig else "real"
    curve_note = (
        "virtual devices share one CPU, so the curve proves "
        "identity/transfer, not speedup"
        if cpu_rig
        else "real devices: the curve is a genuine scaling measurement"
    )
    return "\n".join(
        [
            f"| multichip {scale}: steady storm p50 across mesh sizes "
            f"({md.get('platform')}, {md.get('devices')} {dev_kind} "
            f"devices) | {curve} — placements "
            f"{'bit-identical' if ident_ok else 'DIVERGED'} across sizes; "
            f"{curve_note} |",
            f"| multichip {scale}: donated persistent residents | "
            f"pre-pass packed-state buffers consumed in place across "
            f"every mesh size: {'YES' if don_ok else 'NO'} (runtime "
            f"buffer-reuse probe; graftlint IR005 proves it statically) |",
            f"| multichip {scale}: steady-pass host→device upload | "
            f"{max_up:.4f} MB/pass vs {full:.2f} MB full packed-grid "
            f"upload ({(max_up / full * 100) if full else 0:.2f}%) |",
        ]
    )


def extra_block(src: Path) -> str:
    """Dispatch an extra record file by its metric prefix."""
    d = json.loads(src.read_text())
    if "parsed" in d:
        d = d["parsed"]
    metric = d.get("metric", "")
    if metric.startswith("cold_start"):
        return cold_block(d)
    if metric.startswith("estimator512_wire"):
        return estimator_block(d)
    if metric.startswith("observability_wave"):
        return obs_block(d)
    if metric.startswith("chaos_storm"):
        return chaos_block(d)
    if metric.startswith("quota_surge"):
        return quota_block(d)
    if metric.startswith("preempt_storm"):
        return preempt_block(d)
    if metric.startswith("multichip_scaling"):
        return multichip_block(d)
    raise SystemExit(f"{src}: unrecognized bench record metric {metric!r}")


def rewrite(path: Path, body: str, marker: str = "bench") -> None:
    text = path.read_text()
    pat = _marker_re(marker)
    if not pat.search(text):
        raise SystemExit(f"{path}: no {marker} markers")
    text = pat.sub(lambda m: m.group(1) + body + "\n" + m.group(2), text)
    path.write_text(text)
    print(f"rewrote {path} [{marker}]")


def _marker_re(marker: str) -> "re.Pattern":
    return re.compile(
        rf"(<!-- {marker}:begin[^>]*-->\n).*?(<!-- {marker}:end -->)", re.S
    )


def env_table() -> str:
    """The generated env-var table (karmada_tpu.utils.flags is the single
    source of truth; graftlint GL003 keeps the READ sites honest)."""
    sys.path.insert(0, str(ROOT))
    from karmada_tpu.utils.flags import render_env_table

    return (
        "_Generated from `karmada_tpu/utils/flags.py` ENV_FLAGS by "
        "`tools/docs_from_bench.py --env-table` — regenerate, don't "
        "hand-edit._\n\n" + render_env_table()
    )


def check_env_table() -> None:
    """Fail loudly when the committed OPERATIONS.md env table drifted from
    the flags registry — runs on EVERY doc regeneration."""
    path = ROOT / "docs" / "OPERATIONS.md"
    m = _marker_re("envflags").search(path.read_text())
    if not m:
        raise SystemExit(
            f"{path}: no envflags markers — restore the Environment "
            "variables section and run "
            "`python tools/docs_from_bench.py --env-table`"
        )
    committed_body = m.group(0).split("-->\n", 1)[1].rsplit("<!--", 1)[0]
    if committed_body.strip() != env_table().strip():
        raise SystemExit(
            f"{path}: env table drifted from karmada_tpu/utils/flags.py "
            "ENV_FLAGS — run `python tools/docs_from_bench.py --env-table`"
        )


def metrics_table() -> str:
    """The generated metric-families table (karmada_tpu.utils.metrics
    ``registry`` is the single source of truth; graftlint GL006 keeps the
    names prefixed and unique)."""
    sys.path.insert(0, str(ROOT))
    from karmada_tpu.utils.metrics import render_families_table

    return (
        "_Generated from the `karmada_tpu/utils/metrics.py` registry by "
        "`tools/docs_from_bench.py --metrics-table` — regenerate, don't "
        "hand-edit._\n\n" + render_families_table()
    )


def check_metrics_table() -> None:
    """Fail loudly when the committed OPERATIONS.md metric-families table
    drifted from the live registry (a family the table misses is a family
    operators won't know to scrape) — runs on EVERY doc regeneration,
    same pattern as the env-flag gate."""
    path = ROOT / "docs" / "OPERATIONS.md"
    m = _marker_re("metricfamilies").search(path.read_text())
    if not m:
        raise SystemExit(
            f"{path}: no metricfamilies markers — restore the "
            "Observability metric-families section and run "
            "`python tools/docs_from_bench.py --metrics-table`"
        )
    committed_body = m.group(0).split("-->\n", 1)[1].rsplit("<!--", 1)[0]
    if committed_body.strip() != metrics_table().strip():
        raise SystemExit(
            f"{path}: metric-families table drifted from "
            "karmada_tpu/utils/metrics.py registry — run "
            "`python tools/docs_from_bench.py --metrics-table`"
        )


def span_table() -> str:
    """The generated span-taxonomy table (karmada_tpu.utils.tracing
    SPAN_NAMES is the single source of truth; graftlint GL008 keeps the
    recording sites honest)."""
    sys.path.insert(0, str(ROOT))
    from karmada_tpu.utils.tracing import render_span_table

    return (
        "_Generated from `karmada_tpu/utils/tracing.py` SPAN_NAMES by "
        "`tools/docs_from_bench.py --span-table` — regenerate, don't "
        "hand-edit._\n\n" + render_span_table()
    )


def check_span_table() -> None:
    """Fail loudly when the committed OPERATIONS.md span-taxonomy table
    drifted from the SPAN_NAMES registry (a span the table misses is a
    span operators can't read in a dumped wave) — runs on EVERY doc
    regeneration, same pattern as the env-flag gate."""
    path = ROOT / "docs" / "OPERATIONS.md"
    m = _marker_re("spantaxonomy").search(path.read_text())
    if not m:
        raise SystemExit(
            f"{path}: no spantaxonomy markers — restore the span-taxonomy "
            "section and run `python tools/docs_from_bench.py "
            "--span-table`"
        )
    committed_body = m.group(0).split("-->\n", 1)[1].rsplit("<!--", 1)[0]
    if committed_body.strip() != span_table().strip():
        raise SystemExit(
            f"{path}: span-taxonomy table drifted from "
            "karmada_tpu/utils/tracing.py SPAN_NAMES — run "
            "`python tools/docs_from_bench.py --span-table`"
        )


def history_table() -> str:
    """The generated wave-row schema table (karmada_tpu.utils.history
    ``HISTORY_SERIES`` is the single source of truth; graftlint GL009
    keeps each series' source reference honest)."""
    sys.path.insert(0, str(ROOT))
    from karmada_tpu.utils.history import render_history_schema_table

    return (
        "_Generated from `karmada_tpu/utils/history.py` HISTORY_SERIES "
        "by `tools/docs_from_bench.py --history-table` — regenerate, "
        "don't hand-edit._\n\n" + render_history_schema_table()
    )


def check_history_schema() -> None:
    """Fail loudly when the committed OPERATIONS.md wave-row schema
    table drifted from the HISTORY_SERIES registry (a series the table
    misses is a series operators can't read off /debug/history) — runs
    on EVERY doc regeneration, same pattern as the env-flag gate."""
    path = ROOT / "docs" / "OPERATIONS.md"
    m = _marker_re("historyschema").search(path.read_text())
    if not m:
        raise SystemExit(
            f"{path}: no historyschema markers — restore the Telemetry "
            "history section and run `python tools/docs_from_bench.py "
            "--history-table`"
        )
    committed_body = m.group(0).split("-->\n", 1)[1].rsplit("<!--", 1)[0]
    if committed_body.strip() != history_table().strip():
        raise SystemExit(
            f"{path}: wave-row schema table drifted from "
            "karmada_tpu/utils/history.py HISTORY_SERIES — run "
            "`python tools/docs_from_bench.py --history-table`"
        )


def reasons_table() -> str:
    """The generated reason-taxonomy table (karmada_tpu.utils.reasons
    ``REASONS`` is the single source of truth; graftlint GL010 keeps the
    emission sites honest)."""
    sys.path.insert(0, str(ROOT))
    from karmada_tpu.utils.reasons import render_reasons_table

    return (
        "_Generated from `karmada_tpu/utils/reasons.py` REASONS by "
        "`tools/docs_from_bench.py --reasons-table` — regenerate, don't "
        "hand-edit._\n\n" + render_reasons_table()
    )


def check_reasons_table() -> None:
    """Fail loudly when the committed OPERATIONS.md reason-taxonomy
    table drifted from the REASONS registry (a reason the table misses
    is a reason operators can't decode off /debug/explain) — runs on
    EVERY doc regeneration, same pattern as the env-flag gate."""
    path = ROOT / "docs" / "OPERATIONS.md"
    m = _marker_re("reasontaxonomy").search(path.read_text())
    if not m:
        raise SystemExit(
            f"{path}: no reasontaxonomy markers — restore the Explaining "
            "placements section and run `python tools/docs_from_bench.py "
            "--reasons-table`"
        )
    committed_body = m.group(0).split("-->\n", 1)[1].rsplit("<!--", 1)[0]
    if committed_body.strip() != reasons_table().strip():
        raise SystemExit(
            f"{path}: reason-taxonomy table drifted from "
            "karmada_tpu/utils/reasons.py REASONS — run "
            "`python tools/docs_from_bench.py --reasons-table`"
        )


def delta_safe_table() -> str:
    """The generated delta-safe kernel registry table (the dep tier's
    ``delta_safe_registry`` is the single source of truth; graftlint
    IR006 proves every ``row_coupled`` declaration it summarizes).
    Unlike the other generated tables this one traces the kernel grid —
    it imports jax and costs a few seconds."""
    sys.path.insert(0, str(ROOT))
    from tools.graftlint.dep import render_delta_safe_table

    return (
        "_Generated from `tools/graftlint/dep.py` `delta_safe_registry` "
        "by `tools/docs_from_bench.py --delta-safe-table` — regenerate, "
        "don't hand-edit._\n\n" + render_delta_safe_table(ROOT)
    )


def check_delta_safe_table() -> None:
    """Fail loudly when the committed DEVELOPMENT.md delta-safe table
    drifted from the analyzer's verdicts (a kernel whose certification
    changed under a refactor must change the committed docs in the same
    PR) — runs on EVERY doc regeneration, same pattern as the env-flag
    gate."""
    path = ROOT / "docs" / "DEVELOPMENT.md"
    m = _marker_re("deltasafe").search(path.read_text())
    if not m:
        raise SystemExit(
            f"{path}: no deltasafe markers — restore the delta-safe "
            "kernel contract section and run "
            "`python tools/docs_from_bench.py --delta-safe-table`"
        )
    committed_body = m.group(0).split("-->\n", 1)[1].rsplit("<!--", 1)[0]
    if committed_body.strip() != delta_safe_table().strip():
        raise SystemExit(
            f"{path}: delta-safe kernel table drifted from the dep "
            "tier's certification registry — run "
            "`python tools/docs_from_bench.py --delta-safe-table`"
        )


def check_ir_registry() -> None:
    """Fail loudly when a kernel family exported from karmada_tpu/ops/ is
    missing from the graftlint IR entry-point registry (or the registry
    carries a stale entry) — runs on EVERY doc regeneration, same pattern
    as the env-flag table gate. Pure AST on the ops side and a plain
    import of the registry module: no jax needed."""
    sys.path.insert(0, str(ROOT))
    from tools.graftlint.ir import ops_registry_drift

    unregistered, stale = ops_registry_drift(ROOT)
    if unregistered or stale:
        raise SystemExit(
            "tools/graftlint/ir.py ENTRY_POINTS drifted from the "
            "karmada_tpu/ops exports — "
            f"exported but unregistered: {unregistered}, registered but "
            f"no longer exported: {stale}; register the kernel (with a "
            "spec builder) or drop the stale entry"
        )


#: the generated-table modes:
#: flag -> (marker, body builder, drift check, target doc)
_TABLE_MODES = {
    "--env-table": ("envflags", env_table, check_env_table,
                    "docs/OPERATIONS.md"),
    "--metrics-table": ("metricfamilies", metrics_table,
                        check_metrics_table, "docs/OPERATIONS.md"),
    "--span-table": ("spantaxonomy", span_table, check_span_table,
                     "docs/OPERATIONS.md"),
    "--history-table": ("historyschema", history_table,
                        check_history_schema, "docs/OPERATIONS.md"),
    "--reasons-table": ("reasontaxonomy", reasons_table,
                        check_reasons_table, "docs/OPERATIONS.md"),
    "--delta-safe-table": ("deltasafe", delta_safe_table,
                           check_delta_safe_table,
                           "docs/DEVELOPMENT.md"),
}


def _check_all(skip: str = "") -> None:
    """Every generated table's drift guard (minus the one just
    rewritten) + the IR registry gate — run on EVERY doc regeneration."""
    for flag, (_marker, _body, check, _doc) in _TABLE_MODES.items():
        if flag != skip:
            check()
    check_ir_registry()


def main() -> None:
    if len(sys.argv) == 2 and sys.argv[1] in _TABLE_MODES:
        flag = sys.argv[1]
        marker, body, _check, doc = _TABLE_MODES[flag]
        rewrite(ROOT / doc, body(), marker)
        _check_all(skip=flag)
        return
    src = Path(sys.argv[1])
    d = json.loads(src.read_text())
    if "parsed" in d:  # the driver's BENCH_r{N}.json wrapper
        d = d["parsed"]
    names = src.name
    body = block(d)
    # optional extra records: bench.py --cold-start / --estimator-only
    for extra in sys.argv[2:]:
        extra_src = Path(extra)
        body += "\n" + extra_block(extra_src)
        names += f" {extra_src.name}"
    body = (
        f"_Generated by `tools/docs_from_bench.py {names}` — regenerate, "
        f"don't hand-edit._\n\n" + body
    )
    rewrite(ROOT / "docs" / "OPERATIONS.md", body)
    rewrite(ROOT / "BASELINE.md", body)
    _check_all()


if __name__ == "__main__":
    main()
