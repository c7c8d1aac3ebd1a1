"""Device seconds of ``jit__fleet_pass`` by ``jax.named_scope`` stage (PR 25;
PERF.md section 5). A builder's tool, not part of the benchmark and read by
no metric: it builds a cell the way ``benchmark.run`` does, warms its ring,
takes one ``jax.profiler`` trace of a few waves, and reduces the TPU plane's
"XLA Ops" line to SELF seconds per scope. The trace's events carry no
``op_name``, so ``_fleet_pass`` is lowered and compiled for the very
arguments the engine dispatched, and its HLO text joins instruction names to
scopes. An executable loaded from a persistent cache written before the
scopes existed carries none: run with a fresh ``JAX_COMPILATION_CACHE_DIR``.
From the repo root, on the chip:

    JAX_COMPILATION_CACHE_DIR=/tmp/fresh python3 tools/scope_profile.py \
        rebalance-100kx100.drift 3300000601 16 [--rehearse] [--skip-hlo] \
        [--kernel=_fleet_select]

``--kernel`` names another jitted kernel of ``scheduler/fleet.py`` to join
scopes for (PR 32: ``_fleet_select``, whose stages are ``select.*`` scopes
inside ``fleet.select``); instruction names repeat across modules, so read
the rows of that kernel's module only. Writes ``chiprun_out/scope_profile.json``
(per-scope and per-op seconds a wave), ``chiprun_out/spans_<cell>.json`` (the
program's spans of those waves) and ``chiprun_out/<kernel>_hlo.txt``.
``--rehearse`` runs the cell's tiny sizes on whatever device is there (no TPU
plane: the tables come out empty).
"""

import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # tools/..
sys.path.insert(0, ROOT)
os.environ.setdefault("KARMADA_TPU_CACHE_MIN_COMPILE_SECS", "0")

from benchmark import run  # noqa: E402

SCOPE = re.compile(r"(?:fleet|select)\.[a-z]+")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = ")
OPNAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"calls=(%[\w.\-]+)")
COMP = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+) \(.*\{\s*$")


def scope_of(op_name):
    m = SCOPE.findall(op_name or "")
    return m[-1] if m else "(no scope)"


def scope_map(hlo_text):
    """instruction name -> (scope of its own op_name, {scopes of the
    instructions of the computation it calls})."""
    own, calls, inner = {}, {}, collections.defaultdict(set)
    comp = None
    for line in hlo_text.splitlines():
        c = COMP.match(line)
        if c:
            comp = c.group(1)
            continue
        m = INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = OPNAME.search(line)
        sc = scope_of(op.group(1) if op else "")
        own[name] = sc
        if comp is not None:
            inner[comp].add(sc)
        cl = CALLS.search(line)
        if cl:
            calls[name] = cl.group(1)
    return {n: (sc, sorted(inner.get(calls.get(n), ()))) for n, sc in own.items()}


def self_times(events):
    """[(event, self_ns)] by interval nesting on one line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [event, child_ns]
    for e in evs:
        while stack and stack[-1][0][1] + stack[-1][0][2] <= e[1]:
            top, child = stack.pop()
            out.append((top, max(0, top[2] - child)))
        if stack:
            stack[-1][1] += e[2]
        stack.append([e, 0])
    while stack:
        top, child = stack.pop()
        out.append((top, max(0, top[2] - child)))
    return out


def main():
    cell, seed, n_waves = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    import jax
    from karmada_tpu.utils import compilecache

    compilecache.enable()
    bench, entry, cfg, traffic = run.load_cell(cell, "--rehearse" in sys.argv)
    import karmada_tpu.scheduler.fleet as fleet_mod

    kernel = next((a.split("=", 1)[1] for a in sys.argv
                   if a.startswith("--kernel=")), "_fleet_pass")
    real_pass = getattr(fleet_mod, kernel)
    last_call = {}

    def spy(*args, **kw):
        last_call["avals"] = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                              for a in args]
        last_call["kw"] = kw
        return real_pass(*args, **kw)

    setattr(fleet_mod, kernel, spy)
    dep, mix = run.build(cfg, traffic, seed, run.log)
    dep.setup()
    mix.build()
    annotate = jax.profiler.TraceAnnotation
    g, _ = run.warm_ring(dep, mix, annotate)
    d = tempfile.mkdtemp(prefix="scope-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    from karmada_tpu.utils.tracing import tracer

    tracer.clear()
    t0 = time.perf_counter()
    waves = []
    for _ in range(n_waves):
        mix.prepare(g)
        a = time.perf_counter()
        mix.wave(g, annotate)
        waves.append((a, time.perf_counter()))
        g += 1
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"spans_{cell}.json"), "w") as f:
        json.dump({"waves": waves, "spans": tracer.dump(),
                   "dropped": tracer.dropped_total}, f)
    from jax.profiler import ProfileData

    smap = {}
    if last_call and "--skip-hlo" not in sys.argv:
        hlo = real_pass.lower(*last_call["avals"], **last_call["kw"]
                              ).compile().as_text()
        with open(os.path.join(ROOT, "chiprun_out",
                               f"{kernel.strip('_')}_hlo.txt"), "w") as f:
            f.write(hlo[:8_000_000])
        smap = scope_map(hlo)
        print("hlo instructions", len(smap), "scoped",
              sum(1 for v in smap.values() if v[0] != "(no scope)"))
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    pd = ProfileData.from_file(path)
    by_scope = collections.Counter()
    by_op = collections.Counter()
    modules = collections.Counter()
    stat_keys = collections.Counter()
    samples = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        print("plane", plane.name, [ln.name for ln in plane.lines])
        for ln in plane.lines:
            if ln.name == "XLA Modules":
                for ev in ln.events:
                    modules[ev.name.split("(")[0]] += ev.duration_ns
            if ln.name != "XLA Ops":
                continue
            rows = []
            for ev in ln.events:
                stats = {k: v for k, v in ev.stats}
                rows.append((ev.name, int(ev.start_ns), int(ev.duration_ns),
                             stats))
            for (name, _s, _d, stats), self_ns in self_times(rows):
                for k in stats:
                    stat_keys[k] += 1
                text = " ".join([name] + [str(v) for v in stats.values()])
                module = str(stats.get("hlo_module", stats.get(
                    "program_id", "")))
                im = INSTR.match(name)
                own, inner = smap.get(im.group(1) if im else "", ("?", []))
                # a fusion's own op_name is its root's; where that has no
                # scope and all the fused instructions share one, take it
                named = [x for x in inner if x != "(no scope)"]
                scope = own
                if own in ("(no scope)", "?") and len(named) == 1:
                    scope = named[0]
                by_scope[(module, scope)] += self_ns
                by_op[(module, scope, "+".join(inner), name[:200])] += self_ns
                if len(samples) < 12 and self_ns > 0:
                    samples.append({"name": name, "self_ns": self_ns,
                                    "stats": {k: str(v)[:300]
                                              for k, v in stats.items()}})
    out = {
        "cell": cell, "waves": n_waves, "wall_s": wall,
        "modules_s_per_wave": {k: v / 1e9 / n_waves
                               for k, v in modules.most_common()},
        "scope_s_per_wave": [[m, s, v / 1e9 / n_waves]
                             for (m, s), v in by_scope.most_common()],
        "top_ops_s_per_wave": [[m, s, i, n, v / 1e9 / n_waves]
                               for (m, s, i, n), v in by_op.most_common(80)],
        "stat_keys": dict(stat_keys), "samples": samples,
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "scope_profile.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "wall_s", "modules_s_per_wave", "scope_s_per_wave", "stat_keys")},
        indent=1))
    for row in out["top_ops_s_per_wave"][:30]:
        print(f"{row[4] * 1e3:8.3f} ms  {row[1]:<14} [{row[2]}] {row[3][:110]}")


if __name__ == "__main__":
    main()
