#!/usr/bin/env python3
"""Benchmark entry: one run of one cell, one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for.  It refuses to run (exit 2, no result) without a TPU, with
fewer chips than the cell needs, or on a device that ``bench/peaks.json``
does not list.  JAX's compilation cache is kept in the checkout's
``.jax_cache/``.  With ``--trace 0`` the line carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer ones; the numbers
the correctness check compared come last, on stderr too.
"""
import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import pathlib                                               # noqa: E402
import sys                                                   # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    import harness
    cell = harness.load_cell(ROOT, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[bench] no TPU: JAX found {devs[0].platform}", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"[bench] {args.workload} needs {cell.chips} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    harness.peak_for(devs[0].device_kind)
    harness.use_compile_cache(ROOT)

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, devs[:cell.chips])
    for k, v in out["check"].items():
        print(f"[check] {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
