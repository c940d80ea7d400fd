#!/usr/bin/env python3
"""Find the highest open-loop rate a cell sustains: one set-up, then one
window per rate, drained between rates, in one process on the chip.  A
rate is sustained while the requests in the system at the window's close
stay near the slot count and the time to first token of the window's last
third stays near its first third's.  Each rate is served turns of its
own, with the same histories, sizes and gaps: a turn an earlier rate
served would be found whole in the prefix cache, which no run sees.  A
rate whose window compiled anything (``compiles``) reads nothing sound.
Not part of a benchmark run; its result is written into the traffic file
by hand, once.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.3,0.5,0.7
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def fresh_traffic(mix: dict, seed: int, vocab: int, salt: int):
    """The mix's ``Traffic`` for ``seed`` whose turns are drawn afresh for
    ``salt``; histories, sizes and arrival gaps are the seed's own."""
    from chipbench.traffic import Traffic

    class Fresh(Traffic):
        def _tokens(self, tag, n):
            if tag[0] == "turn":
                tag = tag + ("rate", salt)
            return super()._tokens(tag, n)

    return Fresh(mix, seed, vocab)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = run.prepare()
    import jax
    from chipbench import cell as cellmod
    from chipbench import device, session
    from chipbench.traffic import Traffic, percentile
    meter = device.CompileMeter()
    c = cellmod.find_cell(bench, args.workload)
    device.require_devices(c["chips"])
    conf = cellmod.load_json(cellmod.HERE / "configs" / f"{c['config']}.json")
    mix = cellmod.load_json(cellmod.HERE / "traffic" / f"{c['traffic']}.json")
    ref = session.load_reference(conf["reference"])
    with session.precision_scope(conf):
        cfg = session.model_config(conf)
        w = ref.init_weights(conf["model"], args.seed, conf.get("init"))
        qparams, _, _, _ = session.quantize(conf, cfg, w, args.seed)
        del w
        engine = session.build_engine(conf, cfg, qparams)
        base = Traffic(mix, args.seed, cfg.vocab)
        session.warm_up(engine, cfg.vocab, base.chunk_lengths(
            engine.pool.prefill_chunk, engine.pool.block_size), args.seed,
            cow=bool(base.histories))
        session.prefill_histories(engine, base.histories)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            m = copy.deepcopy(mix)
            m["arrivals"]["rate_per_s"] = rate
            tr = fresh_traffic(m, args.seed, cfg.vocab, i)
            compiles0 = meter.count
            win = session.Window(engine, tr, session.Spans(False))
            win.run(args.seconds)
            lo, hi = win.start, win.end
            third = (hi - lo) / 3
            ttft = {}
            for part in (0, 2):
                a, b = lo + part * third, lo + (part + 1) * third
                seen = [r.times[0] - r.due for r in win.recs.values()
                        if a <= r.due < b and r.times]
                miss = [hi - r.due for r in win.recs.values()
                        if a <= r.due < b and not r.times]
                ttft[part] = percentile(seen, 50, miss) if seen or miss \
                    else float("nan")
            due = [r for r in win.recs.values() if lo <= r.due < hi]
            print(json.dumps({
                "rate": rate, "due": len(due),
                "compiles": meter.count - compiles0,
                "finished": sum(r.done for r in due),
                "in_system_at_close": sum(not r.done for r in due),
                "ttft_p50_first_third_s": ttft[0],
                "ttft_p50_last_third_s": ttft[2],
                "occupancy": sum(engine.stats["occupancy"][
                    win.stats0["occupancy"]:]) / max(
                    1, len(engine.stats["occupancy"])
                    - win.stats0["occupancy"])}), flush=True)
            session.drain(engine)
    jax.block_until_ready(engine.caches)
    return 0


if __name__ == "__main__":
    sys.exit(main())
