"""The control of each cell: the plain reference put in the program's place
with one guarantee of the configuration broken, fed through the cell's own
comparison (the traffic module's ``check``). It has to come out as not
correct; the benchmark's own runs never run it. The system states no
precision (the division is exact integer arithmetic), so there is no lower
precision to step down to.

- ``drift``: every compared wave answered with the reference of the wave
  before it (guarantee broken: placements follow the snapshot of THEIR
  wave);
- ``rebalancer``: the last turn of the ring acknowledged, every stamp set
  as a sound run sets it, and not acted on: bindings and Works of a turn
  before (guarantee broken: every named binding re-divided over the
  availability of its wave, and its Works rendered).

    python -m benchmark.control --workload <cell> --seeds 1,2,3 [--waves 40]

It builds neither engine nor plane and needs no chip: generator, reference
and the cell's own comparison only."""

from __future__ import annotations

import argparse
import json

from . import run


def control_checks(workload: str, seed: int, waves: int,
                   rehearse: bool = False) -> dict:
    _, _, cfg, traffic = run.load_cell(workload, rehearse)
    dep, mix = run.build(cfg, traffic, seed, lambda m: None)
    dep.generate()
    mix.generate()
    return mix.check(mix.control_collected(waves))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--waves", type=int, default=40)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    failed_all = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        checks = control_checks(args.workload, seed, args.waves, args.rehearse)
        checks.pop("_failed", None)
        ok = run.verdict(checks)
        failed_all = failed_all and not ok
        print(f"control {args.workload} seed={seed} correct={ok} "
              + json.dumps({k: [c["value"], c["limit"]] for k, c in checks.items()}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    raise SystemExit(main())
