"""One run of one cell: find its pieces by name, set up, measure, check.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<config>
.json``, with the plain reference it names under ``reference/``), a traffic
mix (``traffic/<traffic>.json``) and its limits (``limits/<cell>.json``).
A metric is read by ``metrics/<name>.py``, or, for a name ``<stem>.<cell
part>``, by ``metrics/<stem>.py``.  Adding any of these is adding a file.

A configuration's ``model`` object is the program's ``ModelConfig``, key
for key (``session.model_config``): any field of it, a nested object as
the dataclass its field holds (``moe`` as ``MoEConfig``), a list as a
tuple, ``family`` ``"dense"`` unless given.  A key that is no field stops
the run.  A mixture-of-experts model is cut to one chip's share of an
expert-parallel deployment (the ``model-configs`` guide, section 4):
``model.moe.n_experts`` counts the experts held on this chip and is
listed in ``reduced``; the router keeps its published width, which the
harness reads from the router's weight (``(d, E_router)``), and its
experts per token (``top_k``); the file states the published expert count
and the deployment beside them.  Each held expert stack is counted as one
grouped matrix of the fused kernel (``work.QMatrix.groups``, ``share``),
and a token's operations count the held experts' expected share of its
``top_k`` (``work.dense_token_flops``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

from . import check, device, session, tracefile
from .traffic import Traffic

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
OUT = HERE / ".out"
SAMPLE = "sample_requests"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def metric_names(bench: dict, cell: str, trace: bool) -> list[str]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones:
    those that list the cell, or list no cells at all."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m["name"] for m in group
            if "workloads" not in m or cell in m["workloads"]]


def units(bench: dict) -> dict:
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def reader(name: str, where: Path = HERE / "metrics"):
    """The ``read(ctx)`` function of metric ``name``."""
    path = where / f"{name}.py"
    if not path.exists():
        path = where / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Ctx:
    """What a metric reader may read: the run's timings, the window's
    record, the engine's counters, the reduced trace and the work
    inputs."""

    def __init__(self, **kw):
        self.notes: list = []
        self.__dict__.update(kw)

    @property
    def window_s(self) -> float:
        return self.win.end - self.win.start

    def note(self, key, value):
        self.notes.append((key, value))

    def tokens_in_window(self) -> int:
        lo, hi = self.win.start, self.win.end
        return sum(1 for r in self.win.recs.values() for t in r.times
                   if lo < t <= hi)

    def token_gaps(self) -> list:
        lo, hi = self.win.start, self.win.end
        out = []
        for r in self.win.recs.values():
            for a, b in zip(r.times, r.times[1:]):
                if lo < b <= hi:
                    out.append(b - a)
        return out

    def ttfts(self):
        """TTFTs (s) of the requests due in the window, and lower bounds
        for those whose first token had not come when it closed."""
        lo, hi = self.win.start, self.win.end
        seen, missing = [], []
        for r in self.win.recs.values():
            if lo <= r.due < hi:
                if r.times and r.times[0] <= hi:
                    seen.append(r.times[0] - r.due)
                else:
                    missing.append(hi - r.due)
        return seen, missing

    def window_delta(self, key: str) -> float:
        return self.win.stats1.get(key, 0) - self.win.stats0.get(key, 0)

    def window_list(self, key: str) -> list:
        full = self.stats.get(key, [])
        return full[self.win.stats0.get(key, 0):self.win.stats1.get(key, 0)]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def in_use() -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0))


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, *, t0: float | None = None, conf: dict | None = None,
             mix: dict | None = None, limits: dict | None = None,
             peaks: dict | None = None, bits: float | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object.  The
    device check is the caller's (``run.py``); tests pass the pieces
    (``conf``, ``mix``, ``limits``, ``peaks``) in place of the files.
    ``bits`` quantizes to another budget than the configuration states:
    the control (``control.py``) and nothing else."""
    import jax
    t0 = time.perf_counter() if t0 is None else t0
    cell = find_cell(bench, name)
    where = None                  # the configuration's file, for its errors
    if conf is None:
        where = str(HERE / "configs" / f"{cell['config']}.json")
        conf = load_json(where)
    mix = mix or load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = limits or load_json(HERE / "limits" / f"{name}.json")
    dev = device.describe(jax.devices())
    peaks = peaks or device.peaks(dev["kind"])
    meter = device.CompileMeter()
    ref = session.load_reference(conf["reference"])
    model = conf["model"]
    times: dict = {}

    with session.precision_scope(conf):
        cfg = session.model_config(conf, where)
        ts = time.perf_counter()
        weights = ref.init_weights(model, seed, conf.get("init"))
        jax.block_until_ready(weights)
        times["weights_s"] = time.perf_counter() - ts
        qparams, rep, times["calibrate_s"], times["quantize_s"] = \
            session.quantize(conf, cfg, weights, seed, bits)
        del weights
        gc.collect()
        log(f"quantized {rep.n_layers} matrices to {rep.avg_bits!r} avg bits"
            f" (asked {rep.requested_avg_bits}): calibrate "
            f"{times['calibrate_s']!r}s, calibrate+quantize "
            f"{times['quantize_s']!r}s, weights {times['weights_s']!r}s")
        qmats = session.quantized_matrices(qparams, cfg)
        experts = session.experts(qparams)
        log(f"device bytes in use with the quantized model: {in_use()}")
        ts = time.perf_counter()
        engine = session.build_engine(conf, cfg, qparams)
        log(f"device bytes in use with the engine and its KV arena: "
            f"{in_use()}")
        traffic = Traffic(mix, seed, model["vocab"])
        lengths = traffic.chunk_lengths(engine.pool.prefill_chunk,
                                        engine.pool.block_size)
        cows = session.warm_up(engine, model["vocab"], lengths, seed,
                               cow=bool(traffic.histories))
        times["warm_up_s"] = time.perf_counter() - ts
        ts = time.perf_counter()
        session.prefill_histories(engine, traffic.histories)
        times["histories_s"] = time.perf_counter() - ts
        log(f"warm-up {times['warm_up_s']!r}s (chunk lengths {lengths}, "
            f"{cows} copy-on-write); session histories "
            f"{times['histories_s']!r}s ({len(traffic.histories)})")

        compiles0 = meter.count
        spans = session.Spans(trace)
        win = session.Window(engine, traffic, spans)
        tdir = OUT / "trace"
        marks = {}

        def on_open():
            marks["open"] = time.perf_counter()
            if trace:
                shutil.rmtree(tdir, ignore_errors=True)
                jax.profiler.start_trace(str(tdir))
                marks["ann"] = jax.profiler.TraceAnnotation("bench.window")
                marks["ann"].__enter__()

        win.run(seconds, on_open=on_open)
        if trace:
            marks["ann"].__exit__(None, None, None)
            ts = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: stop_trace {time.perf_counter() - ts!r}s")
    times["setup_s"] = marks["open"] - t0
    times["ramp_s"] = marks["open"] - t0 - sum(
        times[k] for k in ("weights_s", "quantize_s", "warm_up_s",
                           "histories_s"))
    compiles = meter.count - compiles0
    log(f"window {win.end - win.start!r}s, {len(win.steps)} steps, "
        f"{len(win.recs)} requests submitted; backend compiles in the "
        f"window: {compiles}; persistent cache {dict(meter.cache)}")
    stats_final = dict(engine.stats)
    # what serving holds at the window's close: the quantized weights and
    # the whole KV arena the pool reserves; the process's own peak is set
    # by the float32 model in set-up, which no deployment holds
    mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("bytes_in_use", 0))
    al = engine.allocator
    log(f"device bytes in use at the window's close: "
        f"{dev['memory_peak_bytes']} (process peak, set-up included: "
        f"{mem.get('peak_bytes_in_use')}); KV blocks at the close: "
        f"{al.num_blocks - 1 - al.free_blocks} held by requests, "
        f"{al.cached_idle_blocks} cached idle, of {al.num_blocks - 1}")

    red = events = None
    if trace:
        ts = time.perf_counter()
        events = tracefile.load(tdir)
        tr = time.perf_counter()
        red = tracefile.reduce(events)
        dev["busy_s"] = red.busy_ns / 1e9
        dev["window_s"] = red.window_ns / 1e9
        log(f"trace: {len(events)} events read in {tr - ts!r}s and reduced"
            f" in {time.perf_counter() - tr!r}s; kernels {red.kernel_ns}, "
            f"modules {red.module_ns}")
    late = [r.submitted - r.due for r in win.recs.values()
            if win.start <= r.due < win.end]
    if late:
        log(f"generator lateness: max {max(late) * 1e3!r} ms over "
            f"{len(late)} requests due in the window")

    ctx = Ctx(conf=conf, model=model, mix=mix, win=win, steps=win.steps,
              stats=stats_final, times=times, trace=red, peaks=peaks,
              events=events, qmatrices=qmats, experts=experts,
              slots=engine.pool.max_slots,
              block_size=engine.pool.block_size,
              kv_bytes=2 if conf["precision"]["kv"] == "bf16" else 4)
    unit = units(bench)
    metrics = {}
    ts = time.perf_counter()
    for mname in metric_names(bench, name, trace):
        v = reader(mname)(ctx)
        if v is not None and math.isfinite(v):
            metrics[mname] = {"value": float(v), "unit": unit[mname]}
    for k, v in ctx.notes:
        log(f"{k}: {v}")
    if trace:
        tr = time.perf_counter()
        (OUT / "trace_summary.json").write_text(json.dumps(
            tracefile.summary(events), indent=1, default=str))
        log(f"trace: metrics read in {tr - ts!r}s, summary written in "
            f"{time.perf_counter() - tr!r}s")

    # the output check, with the program's state freed first
    due = [r for r in win.recs.values() if win.start <= r.due < win.end]
    sample = session.sample_finished(win.recs, seed, limits[SAMPLE])
    seqs = [(traffic.request(r.k).prompt, r.tokens) for r in sample]
    del engine, qparams, win.engine, ctx
    gc.collect()
    log(f"device bytes in use once the program's state is freed: "
        f"{in_use()}")
    ts = time.perf_counter()
    weights = ref.init_weights(model, seed, conf.get("init"))
    gaps = check.gaps_for(ref, model, weights, seqs,
                          check.bucket(traffic.longest()),
                          int(mix["output"]["hi"]))
    nums = check.numbers(gaps)
    correct, rows = check.judge(nums, limits)
    log(f"check: {len(seqs)} requests, {nums['tokens']} served tokens "
        f"against the reference in {time.perf_counter() - ts!r}s; widest "
        f"gap {nums['gap_max']!r} (not compared)")

    result = {"correct": bool(correct and compiles == 0),
              "attempted": len(due), "failed": 0, "metrics": metrics,
              "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
    result["check"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    result["check"]["window_compiles"] = {"value": compiles, "limit": 0}
    for k, v, lim in rows:
        log(f"check {k}: {v!r} (limit {lim!r})")
    log(f"check window_compiles: {compiles} (limit 0)")
    return result
