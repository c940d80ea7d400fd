#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  Builds the cell's model from its
configuration with weights made from ``--seed``, calibrates and
RaanA-quantizes it, warms the engine's shapes, then serves the cell's
traffic through ``PagedServer.submit``/``step`` for ``--seconds``.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each compared number with its limit.
Exits non-zero, with no result, unless JAX's first device is a TPU and
there are as many as the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE = HERE / ".jax_cache"


def prepare() -> dict:
    """Read ``BENCHMARK.json``, put the program on the path, and keep JAX's
    compile cache in one fixed directory inside the checkout, which the
    program's own ``use_compile_cache`` then takes too.  Every program is
    kept, however quick to compile, so that only a cell's first run in a
    checkout compiles."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax
    from repro.launch.jaxcache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = prepare()
    from chipbench import cell as cellmod
    from chipbench import device
    chips = cellmod.find_cell(bench, args.workload)["chips"]
    device.require_devices(chips)
    result = cellmod.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), t0=T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
