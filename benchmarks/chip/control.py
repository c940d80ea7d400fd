#!/usr/bin/env python3
"""The control of the output check: the cell run with the program's own
quantizer at half the bit budget the configuration states (the next step
down the ladder of weight precisions, as int4 is to int8).  Its numbers are
the upper readings the limits in ``limits/<cell>.json`` sit below; a sound
run's are the lower ones.  Not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

Runs every seed in one process (one chip) and prints one JSON line per
seed with the numbers compared and the limits they meet or fail.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (sets up the program's path and the cache)

SHARE = 0.5          # of the stated bit budget: the next step down


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = run.prepare()
    from chipbench import cell as cellmod
    from chipbench import device
    c = cellmod.find_cell(bench, args.workload)
    device.require_devices(c["chips"])
    conf = cellmod.load_json(cellmod.HERE / "configs" / f"{c['config']}.json")
    bits = conf["quant"]["avg_bits"] * SHARE
    for s in args.seeds.split(","):
        res = cellmod.run_cell(bench, args.workload, int(s), args.seconds,
                               False, bits=bits)
        print(json.dumps({"seed": int(s), "bits": bits,
                          "correct": res["correct"], "check": res["check"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
