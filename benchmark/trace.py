"""The traced run: a jax.profiler trace of a short stretch of the window,
its extraction from the ``.xplane.pb``, and the per-layer readers found by
name under benchmark/metrics/."""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import tempfile
import time

from . import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
KERNELS = ("jit__fleet_pass", "jit__fleet_entries", "jit__fleet_solve",
           "jit__scatter_rows")


class Profile:
    """Profiler over the first stretch of the window: at least ``seconds``
    and ``waves`` waves (traffic file ``trace_seconds`` / ``trace_waves``)."""

    def __init__(self, traffic: dict):
        self.seconds = float(traffic.get("trace_seconds", 3.0))
        self.waves = int(traffic.get("trace_waves", 4))
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.sync = None

    def start(self) -> float:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the plane is Python: tracing every
        opts.enable_hlo_proto = False  # call would be the load itself
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("harness.sync"):
            self.sync = time.perf_counter()
        return time.perf_counter()

    def stop(self) -> float:
        """Stops the profiler; returns the time it had stopped (writing the
        trace out can take seconds, and no wave runs meanwhile)."""
        import jax

        jax.profiler.stop_trace()
        return time.perf_counter()

    def events(self, rehearse: bool = False) -> dict:
        """{'devices': {plane: [(name, start_ns, dur_ns)]}, 'host': [...],
        'lines': {plane: [line names]}} and removes the trace from disk."""
        from jax.profiler import ProfileData

        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return extract(ProfileData.from_file(paths[0]), rehearse)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def extract(pd, rehearse: bool = False) -> dict:
    """``rehearse``: with no TPU plane, the CPU client's threads stand in
    for a device so that a CPU rehearsal walks the same code; a rehearsal
    prints no number read from them."""
    devices, host, lines = {}, [], {}
    for plane in pd.planes:
        names = [ln.name for ln in plane.lines]
        lines[plane.name] = names
        if plane.name.startswith("/device:TPU:"):
            # modules: one event per executed program, named jit_<fn>(hash)
            want = "XLA Modules" if "XLA Modules" in names else "XLA Ops"
            for ln in plane.lines:
                if ln.name == want:
                    devices[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in ln.events
                    ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("harness."):
                        host.append(
                            (ev.name, int(ev.start_ns), int(ev.duration_ns)))
                if rehearse and ln.name.startswith("tf_XLAPjRtCpuClient"):
                    devices.setdefault("rehearsal:cpu", []).extend(
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in ln.events if ev.duration_ns > 0)
    if rehearse:
        devices.setdefault("rehearsal:cpu", [])
    return {"devices": devices, "host": host, "lines": lines}


def reduce_trace(ev: dict, sync_pc: float, waves_pc: list, spans: list) -> dict:
    """Everything the device readers need, over the traced waves."""
    sync = [e for e in ev["host"] if e[0] == "harness.sync"]
    if not sync or not ev["devices"]:
        raise RuntimeError(
            f"the trace holds no device plane or no harness.sync: "
            f"{ev['lines']}")
    offset = sync[0][1] + sync[0][2] - int(sync_pc * 1e9)  # xplane - perf_counter
    lo = int(waves_pc[0][0] * 1e9) + offset
    hi = int(waves_pc[-1][1] * 1e9) + offset
    busy, sums, idle_by = [], {}, {}
    open_spans = [e for e in ev["host"] if e[0] != "harness.sync"] + [
        (s["name"], int(s["start"] * 1e9) + offset, int(s["duration_s"] * 1e9))
        for s in spans
    ]
    for events in ev["devices"].values():
        cut = reduce.clip(events, lo, hi)
        ns, merged = reduce.busy_union(cut)
        busy.append(ns)
        for k, v in reduce.op_sums(cut).items():
            sums[k] = sums.get(k, 0) + v
        for k, v in reduce.attribute_gaps(
                reduce.gaps(merged, lo, hi), open_spans).items():
            idle_by[k] = idle_by.get(k, 0) + v
    n = len(ev["devices"])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "op_s": {k: v / n / 1e9 for k, v in sums.items()},
        "idle_by": {k: v / n for k, v in idle_by.items()},
        "waves": len(waves_pc),
    }


def dump_recording(ev: dict, sync_pc: float, waves_pc: list, path: str,
                   n_waves: int = 2) -> None:
    """Writes the device and harness events of the first ``n_waves`` traced
    waves as a small JSON recording for benchmark/tests, with what an
    independent sweep (+1/-1 edges, not the interval merge) reads from it."""
    sync = [e for e in ev["host"] if e[0] == "harness.sync"][0]
    offset = sync[1] + sync[2] - int(sync_pc * 1e9)
    lo = int(waves_pc[0][0] * 1e9) + offset
    hi = int(waves_pc[n_waves - 1][1] * 1e9) + offset
    device = [list(e) for e in next(iter(ev["devices"].values()))
              if e[1] + e[2] > lo and e[1] < hi]
    host = [list(e) for e in ev["host"] if e[1] + e[2] > lo and e[1] < hi]
    edges = sorted([(max(s, lo), 1) for _, s, d in device]
                   + [(min(s + d, hi), -1) for _, s, d in device])
    busy = depth = 0
    last = lo
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    sums: dict = {}
    for name, s, d in device:
        k = name.split("(")[0]
        sums[k] = sums.get(k, 0) + min(s + d, hi) - max(s, lo)
    with open(path, "w") as f:
        json.dump({"lo": lo, "hi": hi, "device": device, "host": host,
                   "expect": {"busy_ns": busy, "op_sums": sums}}, f)


def context(cfg, traffic, win, spans, profile, device,
            rehearse: bool = False, dump: str = "") -> dict:
    """What a reader may read. Device readings exist only where a profile
    was taken on a chip."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    kind = device["kind"]
    if rehearse:
        kind = next(iter(peaks))
    if kind not in peaks:
        raise RuntimeError(f"device kind {kind!r} is not in benchmark/peaks.json")
    a, b = win["profiled"]
    traced = [(s, e) for s, e in win["waves"] if s >= a and s < b]
    ev = profile.events(rehearse)
    if dump:
        dump_recording(ev, profile.sync, traced, dump)
    red = reduce_trace(ev, profile.sync, traced, spans)
    red["lines"] = {k: v[:12] for k, v in ev["lines"].items()}
    # host readings come from the waves after the profiler stopped: its
    # start and stop sit between waves and are nobody's load
    rest = [(s, e) for s, e in win["waves"] if s >= b]
    if len(rest) < 2:
        raise RuntimeError("the window ended before the profiler stopped; "
                           "nothing is left to read host shares from")
    return {
        "cfg": cfg, "traffic": traffic, "win": win, "spans": spans,
        "waves": rest, "rest_wall": rest[-1][1] - rest[0][0],
        "trace": red, "peak": peaks[kind],
        "kernels": KERNELS,
        "device_extra": {"busy_s": red["busy_s"], "window_s": red["window_s"]},
        "breakdown": {
            "device_ops": reduce.top({k: v * 1e9 for k, v in red["op_s"].items()}),
            "idle_gaps": reduce.top(red["idle_by"]),
        },
    }


def per_layer(bench: dict, cell: str, ctx: dict) -> dict:
    """Each per-layer metric of this cell, by its reader
    benchmark/metrics/<name>.py: ``read(ctx) -> number or None``."""
    e2e = {
        m["name"]: m.get("workloads") for m in bench["end_to_end"]
    }
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        if cells is None and e2e.get(m["moves"]) and cell not in e2e[m["moves"]]:
            continue
        mod = importlib.import_module(
            "benchmark.metrics." + m["name"].replace(".", "_").replace("-", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
