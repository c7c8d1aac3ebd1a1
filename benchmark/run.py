"""One run of one cell: ``python -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``. See benchmark/README.md.

Set-up builds everything from ``--seed`` (data, the program under test, the
ring of ready inputs), warms the whole ring until a full turn compiles
nothing, and only then opens the window. Inside the window the harness calls
the entry point with the next ring element, back to back, and does nothing
else. The comparison with the plain reference runs after the window, after
the peak memory has been read and the program's state dropped."""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import gc
import importlib
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool) -> tuple:
    """(BENCHMARK.json, its entry, config, traffic) of a cell, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    cell = load_json("workloads", f"{name}.json")
    cfg = load_json("configs", f"{cell['config']}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    for d in (cfg, traffic):
        tiny = d.pop("rehearsal", {})
        if rehearse:
            for k, v in tiny.items():
                both = isinstance(v, dict) and isinstance(d.get(k), dict)
                d[k] = {**d[k], **v} if both else v
    return bench, entry, cfg, traffic


def build(cfg: dict, traffic: dict, seed: int, log) -> tuple:
    """(deployment, traffic mix) of a cell: the config's ``driver`` names
    benchmark/drivers/<driver>.py, the traffic's ``kind``
    benchmark/traffic/<kind>.py."""
    dep = importlib.import_module(
        f"benchmark.drivers.{cfg['driver']}").Deployment(cfg, seed, log)
    kind = importlib.import_module(f"benchmark.traffic.{traffic['kind']}")
    if kind.DRIVER != cfg["driver"]:
        raise ValueError(f"traffic kind {traffic['kind']!r} drives "
                         f"{kind.DRIVER!r} deployments, not {cfg['driver']!r}")
    return dep, kind.Traffic(dep, traffic, log)


class GcClock:
    """Seconds the collector ran, by gc.callbacks."""

    def __init__(self):
        self.total = 0.0
        self.count = 0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.total += time.perf_counter() - self._t
            self.count += 1
            self._t = None


def compile_counts() -> dict:
    """The program's compile counter, by (kernel, bucket) label set."""
    from karmada_tpu.utils.metrics import kernel_compiles

    return dict(kernel_compiles.samples())


def compiles_total() -> float:
    return float(sum(compile_counts().values()))


def warm_ring(dep, mix, annotate) -> tuple:
    """Drive the whole ring, in ring order, at least twice, until one full
    turn dispatched no unseen trace and no cap shrink is left to fire.

    A ring whose demand differs from element to element raises and clears a
    shrink desire inside every turn without ever sustaining it. The window
    replays the same turn, so a turn that compiled nothing and shows the
    same pending pattern, wave for wave, as the turn before it is settled:
    what did not fire in two identical turns does not fire in a third."""
    g, before_pattern = 0, None
    for turn in range(8):
        compiled_any, pattern = False, []
        for _ in range(mix.ring):
            before = compile_counts()
            mix.prepare(g)
            t0 = time.perf_counter()
            mix.wave(g, annotate)
            dt = time.perf_counter() - t0
            traced = [k for k, v in compile_counts().items()
                      if v != before.get(k)]
            fresh = bool(traced) or dep.new_trace()
            pattern.append(dep.shrink_pending())
            if fresh or dt > 1.0:
                log(f"setup warm_wave={g} s={dt:.2f} {dep.state()} "
                    f"traced={traced}")
            compiled_any = compiled_any or fresh
            g += 1
        settled = not compiled_any and (
            not pattern[-1] or pattern == before_pattern)
        log(f"setup warm_turn={turn} compiled={compiled_any} "
            f"shrink_pending_waves={sum(pattern)} settled={settled}")
        if turn >= 1 and settled:
            return g, dt
        before_pattern = None if compiled_any else pattern
    raise RuntimeError("the ring never settled: a turn still compiles")


def run_window(dep, mix, g0: int, seconds: float, keep: set, annotate,
               profile=None, gc_clock=None) -> dict:
    """The measured window. Returns the raw readings."""
    waves, cpu, fresh_hits, done = [], [], 0, 0
    g = g0
    c0 = compiles_total()
    prof_span = None
    cpu0 = time.process_time()
    t_open = time.perf_counter()
    deadline = t_open + seconds
    while True:
        if profile is not None and prof_span is None:
            prof_span = [profile.start(), None]
        mix.prepare(g)
        t0 = time.perf_counter()
        done += mix.wave(g, annotate)
        t1 = time.perf_counter()
        waves.append((t0, t1))
        cpu.append(time.process_time())
        fresh_hits += dep.new_trace()
        if g - g0 in keep:
            mix.keep(g)
        g += 1
        if profile is not None and prof_span[1] is None and (
                t1 - prof_span[0] >= profile.seconds and g - g0 >= profile.waves):
            prof_span[1] = profile.stop()
            gc_clock.total = 0.0
            # stopping the profiler can take seconds: the waves after it,
            # which the host readings come from, get their share regardless
            deadline = max(deadline, prof_span[1] + max(2.0, 0.3 * seconds))
        if t1 >= deadline:
            break
    if profile is not None and prof_span[1] is None:
        prof_span[1] = profile.stop()
    if g - 1 - g0 not in keep:
        mix.keep(g - 1)
    return {
        "waves": waves, "done": done, "g_end": g,
        # diagnostics for the log: this process's CPU seconds in each wave
        # (all threads). The chip's machine keeps /proc/stat, the load
        # average and the context-switch counts at 0, so nothing else tells
        # a wave that waited from one that worked
        "cpu": [b - a for a, b in zip([cpu0] + cpu, cpu)],
        "wall": waves[-1][1] - waves[0][0],
        "compiles": compiles_total() - c0, "fresh_hits": fresh_hits,
        "profiled": tuple(prof_span) if prof_span else None,
    }


def end_to_end(win: dict, setup_s: float, names: list) -> dict:
    walls = [b - a for a, b in win["waves"]]
    out = {
        "bindings_per_s": (win["done"] / win["wall"], "bindings/s"),
        "wave_p50_s": (statistics.median(walls), "s"),
        "setup_s": (setup_s, "s"),
    }
    return {k: {"value": out[k][0], "unit": out[k][1]} for k in names}


def device_facts(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devs[:chips]
    ]
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": int(max(peaks)),
    }


def verdict(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if c.get("better", "lower") == "lower"
               else c["value"] >= c["limit"] for c in checks.values())


def main(argv=None, *, rehearse: bool = False) -> dict:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever device: prints counts and "
                         "`correct` only, never a time or a device metric")
    ap.add_argument("--dump-events", default="",
                    help="with --trace 1: write a small recording of the "
                         "trace for benchmark/tests to this path")
    ap.add_argument("--waves", action="store_true",
                    help="print every wave's wall time on earlier lines")
    args = ap.parse_args(argv)
    rehearse = rehearse or args.rehearse

    if not os.path.isdir(os.path.join(ROOT, "karmada_tpu")):
        print("benchmark.run: the program (karmada_tpu/) is not beside "
              "benchmark/; nothing to measure", file=sys.stderr)
        raise SystemExit(3)
    bench, entry, cfg, traffic = load_cell(args.workload, rehearse)
    chips = int(entry["chips"])

    # every program, however small, goes to the persistent cache, so only
    # the first run in a checkout compiles (the program keeps the cache at
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache/<platforms>/)
    os.environ.setdefault("KARMADA_TPU_CACHE_MIN_COMPILE_SECS", "0")
    sys.path.insert(0, ROOT)
    import jax

    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark.run: needs {chips} TPU chip(s), found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(2)
    from karmada_tpu.utils import compilecache

    log(f"cell {args.workload} seed={args.seed} device={devs[0].device_kind} "
        f"cache={compilecache.enable()}")

    annotate = jax.profiler.TraceAnnotation
    dep, mix = build(cfg, traffic, args.seed, log)
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    try:
        t0 = time.perf_counter()
        log(f"setup import_s={t0 - _T0:.2f}")
        dep.setup()
        mix.build()
        t1 = time.perf_counter()
        g0, last_warm_s = warm_ring(dep, mix, annotate)
        log(f"setup build_s={t1 - t0:.2f} warm_s={time.perf_counter() - t1:.2f} "
            f"warm_waves={g0}")

        from karmada_tpu.utils.tracing import tracer

        from . import gen
        from . import trace as trace_mod

        profile = trace_mod.Profile(traffic) if args.trace else None
        # which waves keep their answers: drawn from the seed over the count
        # the warm-up's pace predicts
        est = max(1, int(args.seconds / max(last_warm_s, 1e-4) * 0.8))
        keep = gen.sample_waves(est, int(cfg["check"].get("waves", 0)), args.seed)
        if args.trace:
            tracer.clear()
        gc.collect()
        gc_clock.total, gc_clock.count = 0.0, 0
        setup_s = time.perf_counter() - _T0
        win = run_window(dep, mix, g0, args.seconds, keep, annotate,
                         profile, gc_clock)
        win["gc_s"], win["gc_runs"] = gc_clock.total, gc_clock.count
        win["gc_rest_s"] = gc_clock.total  # reset where the profiler stopped
    finally:
        gc.callbacks.remove(gc_clock)

    walls = [b - a for a, b in win["waves"]]
    log(f"window waves={len(walls)} wall={win['wall']:.4f} "
        f"in_waves={sum(walls):.4f} done={win['done']} "
        f"compiles={win['compiles']} fresh_hits={win['fresh_hits']} "
        f"gc_s={win['gc_s']:.4f} gc_runs={win['gc_runs']} "
        f"min={min(walls):.5f} p50={statistics.median(walls):.5f} "
        f"max={max(walls):.5f}")
    if args.waves:
        log("waves " + " ".join(f"{w:.5f}" for w in walls))
        log("waves_cpu " + " ".join(f"{c:.3f}" for c in win["cpu"]))
    slow = sorted(range(len(walls)), key=lambda i: -walls[i])[:5]
    log("slowest wave:wall/cpu " + " ".join(
        f"{i}:{walls[i]:.5f}/{win['cpu'][i]:.5f}" for i in slow)
        # a run whose waves are all slower: more CPU in them (the cores ran
        # slower) or the same CPU under more wall (the process waited)?
        + f" cpu_in_waves={sum(win['cpu']):.2f}"
        f" cpu_p50={statistics.median(win['cpu']):.3f}")

    device = device_facts(chips)
    spans = tracer.dump() if args.trace else None
    collected = mix.collect()
    mix.free()
    dep.free()
    gc.collect()

    t0 = time.perf_counter()
    checks = mix.check(collected)
    failed = checks.pop("_failed")
    log(f"check reference_s={time.perf_counter() - t0:.2f}")
    correct = verdict(checks)

    result = {
        "correct": bool(correct),
        "attempted": int(win["done"]),
        "failed": int(failed),
        "metrics": {},
        "device": device,
    }
    if args.trace:
        ctx = trace_mod.context(
            cfg, traffic, win, spans, profile, device, rehearse,
            args.dump_events)
        layer = trace_mod.per_layer(bench, entry["name"], ctx)
        for note in ctx.get("notes", []):
            log(note)
        log(f"trace lines={json.dumps(ctx['trace']['lines'])}")
        log(f"trace waves={ctx['trace']['waves']} window_s="
            f"{ctx['trace']['window_s']:.4f} busy_s={ctx['trace']['busy_s']:.4f}")
    if rehearse:
        # counts only: no time, rate or device reading leaves a rehearsal
        result["rehearsal"] = True
        result["waves"] = len(walls)
        if args.trace:
            result["per_layer_read"] = sorted(layer)
    elif args.trace:
        result["metrics"] = layer
        result["device"].update(ctx["device_extra"])
        result["breakdown"] = ctx["breakdown"]
    else:
        names = [
            m["name"] for m in bench["end_to_end"]
            if entry["name"] in m.get("workloads", [entry["name"]])
        ]
        result["metrics"] = end_to_end(win, setup_s, names)
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}={c['value']} limit={c['limit']}")
    log(f"correct={result['correct']}")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
