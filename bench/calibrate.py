#!/usr/bin/env python3
"""Readings that the limits of the correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8 [--control]

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (a short window at the cell's own load), the widest gap of the
served tokens against the float32 reference, and with ``--control`` the
same gap for the tokens that the reference computed in float8 (e4m3)
puts first, at the same positions of the same sequences.  One JSON line
per seed; the limit goes between the largest program reading and the
smallest control reading (``bench/limits/<cell>.json``).  Needs the
cell's chips, like ``bench/run.py``.
"""
import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import json                                                  # noqa: E402
import pathlib                                               # noqa: E402
import sys                                                   # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    cell = harness.load_cell(ROOT, args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("[calibrate] needs the cell's TPU chips", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    t0 = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        keep = {}
        out = harness.run(cell, seed, args.seconds, False, t0,
                          devs[:cell.chips], keep=keep)
        line = {"seed": seed, "program_gap": out["check"]["max_gap"]["value"],
                "tokens": out["check"]["tokens_compared"]["value"],
                "failed": out["failed"], "setup_s":
                out["metrics"].get("setup_s", {}).get("value")}
        if args.control:
            t = time.perf_counter()
            gap, _ = harness.served_gap(cell, keep["params"], keep["sample"],
                                        quant="fp8")
            line["control_gap"] = gap
            line["control_s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        keep.clear()
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
