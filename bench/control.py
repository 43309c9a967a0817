"""Readings that the correctness limits are set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--program]

For each seed: the cell's data, the plain reference, and the control (the
reference computed one precision step below the configuration's, in the
program's place), each compared with the reference by the cell's numbers.
``--program`` also runs one job of the program's timed entry on the same
data.  Prints one JSON line per seed.  The benchmark's own runs never run
this; it exits 2 without a TPU, like them.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def readings(cell, seed: int, devices, program: bool) -> dict:
    from bench.run import make_session
    app, cfg = cell.app, cell.config
    data = app.make_data(cfg, seed)
    out = {"seed": seed}
    if program:
        result = app.run_job(data, cfg, seed, make_session(cell.traffic, devices, False))
    control = app.control(data, cfg, seed)
    ref = app.reference(app.to_host(data), cfg, seed)
    if program:
        out["program"] = app.compare(result, ref, cfg)
    out["control"] = app.compare(control, ref, cfg)
    out["limits"] = cfg["limits"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    from bench import registry
    from bench.run import enable_compile_cache
    cell = registry.resolve(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, devices[: cell.chips], args.program)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
