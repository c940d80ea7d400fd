"""Set-up and the measured window: the only module that drives the serving
program.  It builds the engine as ``launch/serve.py:build_engine`` does,
calibrates and quantizes through ``core.calibrate`` and
``core.pipeline.quantize_model`` as ``serve.load_model`` does, and then
drives ``PagedServer`` through ``submit`` and ``step`` alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import random
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]


def load_reference(name: str):
    """``reference/<name>.py``, the plain reference a configuration names."""
    path = HERE / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(conf: dict):
    from repro.models.config import ModelConfig
    m = conf["model"]
    return ModelConfig(name=conf["name"], family="dense",
                       n_layers=m["n_layers"], d_model=m["d_model"],
                       n_heads=m["n_heads"], n_kv=m["n_kv"], d_ff=m["d_ff"],
                       vocab=m["vocab"], head_dim=m["head_dim"],
                       rope_theta=m["rope_theta"],
                       qk_norm=bool(m.get("qk_norm", False)))


def pool_config(conf: dict):
    import jax.numpy as jnp
    from repro.serve import PoolConfig
    p = dict(conf["pool"])
    p["kv_dtype"] = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        conf["precision"]["kv"]]
    return PoolConfig(**p)


@contextlib.contextmanager
def precision_scope(conf: dict):
    """The matmul precision the configuration states, around everything
    the program traces ("default" leaves XLA's own)."""
    import jax
    mp = conf["precision"]["matmul"]
    if mp == "default":
        yield
    else:
        with jax.default_matmul_precision(mp):
            yield


class Spans:
    """Host spans named ``bench.<name>`` in the profiler's trace, or
    nothing when the run is not traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")


def quantize(conf: dict, cfg, weights: dict, seed: int, bits=None):
    """Calibrate on the paper's zero-shot sentence and RaanA-quantize, as
    ``serve.load_model`` does.  Returns ``(qparams, report, calibrate_s,
    quantize_s)``; both times run until the results are on the device."""
    import jax
    import jax.numpy as jnp
    from repro.core import calibrate as cal
    from repro.core import pipeline as pipe
    from repro.models import transformer as tf
    ref = load_reference(conf["reference"])
    bits = conf["quant"]["avg_bits"] if bits is None else bits
    t0 = time.perf_counter()
    toks = cal.zero_shot_tokens(cfg.vocab, conf["quant"]["calib_tokens"])
    stats = cal.calibrate(
        lambda p, b, ctx: tf.loss_fn(cfg, p, b, ctx=ctx, scan=False),
        weights, [{"tokens": jnp.asarray(toks)}])
    calibrate_s = time.perf_counter() - t0       # stats are host arrays
    qparams, rep = pipe.quantize_model(cfg, weights, stats, bits,
                                       ref.seed_key(seed + 1))
    jax.block_until_ready(qparams)
    return qparams, rep, calibrate_s, time.perf_counter() - t0


def quantized_matrices(qparams) -> list:
    """Every quantized linear's shape as the fused kernel sees it."""
    import jax
    from repro.core.qlinear import QuantizedLinear

    from .work import QMatrix
    is_q = lambda x: isinstance(x, QuantizedLinear)   # noqa: E731
    return [QMatrix(d_keep=q.d_keep, c=q.c, bits=q.bits,
                    d_hat=int(q.signs1.shape[0]),
                    two_blocks=q.signs2 is not None)
            for q in jax.tree.leaves(qparams, is_leaf=is_q) if is_q(q)]


def build_engine(conf: dict, cfg, qparams):
    """``launch/serve.py:build_engine`` with the cell's ``PoolConfig``:
    the fused RHT+qmatmul kernel, the backend's paged-attention choice."""
    from repro.serve import PagedServer
    return PagedServer(cfg, qparams, pool_config(conf), fused=True,
                       paged_kernel=None)


def drain(engine) -> None:
    while engine.poll():
        engine.step()


def prefill_histories(engine, histories) -> None:
    """Serve each session history once (one token out), so its blocks sit
    in the prefix cache before the window."""
    from repro.serve import Request
    for i, h in enumerate(histories):
        engine.submit(Request(rid=-1000 - i, prompt=h, max_new=1))
    drain(engine)


def warm_up(engine, vocab: int, lengths, seed: int, cow: bool) -> int:
    """Compile the cell's own shapes through ``submit``/``step``: one
    request per prefill-chunk length in ``lengths`` (each prefills in one
    chunk, then decodes at the engine's slot count), then, with ``cow``, a
    request that shares part of a cached block, so the prefix cache copies
    on write.  Returns how many copy-on-writes ran."""
    from repro.serve import Request
    rng = np.random.default_rng([int(seed), 0xA11])
    rid = -1
    for n in lengths:
        engine.submit(Request(rid=rid, prompt=rng.integers(
            0, vocab, n).astype(np.int32), max_new=2))
        rid -= 1
    drain(engine)
    if not cow:
        return 0
    bs = engine.pool.block_size
    base = rng.integers(0, vocab, 2 * bs + bs // 2).astype(np.int32)
    engine.submit(Request(rid=rid, prompt=base, max_new=2))
    drain(engine)
    cow0 = engine.stats.get("prefix_cow", 0)
    tail = rng.integers(0, vocab, bs // 2).astype(np.int32)
    engine.submit(Request(rid=rid - 1, prompt=np.concatenate(
        [base[:bs + bs // 4], tail]), max_new=2))
    drain(engine)
    return engine.stats.get("prefix_cow", 0) - cow0


@dataclasses.dataclass
class Rec:
    """What the harness saw of one request."""
    k: int
    plen: int
    max_new: int
    due: float                 # engine clock, s
    submitted: float = 0.0
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class StepRec:
    """One engine iteration inside the window: its end, the prompt tokens
    its chunk prefilled, and the context of each slot its decode served."""
    end: float
    chunk: int
    contexts: list


class Window:
    """Drives the engine as the mix's load generator and keeps the record
    of the window: ``recs`` by request, ``steps`` by iteration, and the
    window's bounds on the engine clock."""

    def __init__(self, engine, traffic, spans: Spans):
        self.engine, self.traffic, self.span = engine, traffic, spans
        self.recs: dict[int, Rec] = {}
        self.steps: list[StepRec] = []
        self._emitted: list = []
        self.start = self.end = None
        self.next_k = 0
        self.stats0 = self.stats1 = None

    def _on_token(self, rid, tok, t):
        r = self.recs[rid]
        self._emitted.append((rid, len(r.times)))
        r.times.append(t)
        r.tokens.append(int(tok))

    def submit(self, due: float | None = None) -> Rec:
        """Offer the mix's next request, due at ``due`` on the engine clock
        (a closed loop's client sends it now: ``None``)."""
        from repro.serve import Request
        with self.span("generator"):
            q = self.traffic.request(self.next_k)
        now = self.engine.now()
        due = now if due is None else due
        rec = Rec(k=q.k, plen=len(q.prompt), max_new=q.max_new, due=due,
                  submitted=now)
        self.recs[q.k] = rec
        with self.span("submit"):
            self.engine.submit(Request(rid=q.k, prompt=q.prompt,
                                       max_new=q.max_new, arrival=due,
                                       on_token=self._on_token))
        self.next_k += 1
        return rec

    def _step(self) -> dict:
        pre = self.engine.stats.get("prefill_tokens", 0)
        self._emitted = []
        with self.span("step"):
            done = self.engine.step()
        with self.span("record"):
            end = self.engine.now()
            for rid in done:
                self.recs[rid].done = True
            if self.start is not None:
                self.steps.append(StepRec(
                    end=end,
                    chunk=self.engine.stats.get("prefill_tokens", 0) - pre,
                    contexts=[self.recs[rid].plen + j
                              for rid, j in self._emitted if j >= 1]))
        return done

    def _open(self):
        self.start = self.engine.now()
        self.stats0 = snapshot(self.engine.stats)

    def run(self, seconds: float, on_open=None) -> None:
        """The closed loop opens its window once every client's first
        request has its first token; the open loop at once.  The window
        closes at the end of the first step past ``seconds``."""
        eng, tr = self.engine, self.traffic
        eng.start_clock(reset=True)
        if tr.loop == "closed":
            for _ in range(tr.clients):
                self.submit()
            first = list(self.recs.values())
            while self.start is None:
                for rid in self._step():
                    self.submit()
                if all(r.times for r in first):
                    if on_open:
                        on_open()
                    self._open()
            while eng.now() < self.start + seconds:
                for rid in self._step():
                    self.submit()
        else:
            if on_open:
                on_open()
            self._open()
            while True:
                now = eng.now()
                if now >= self.start + seconds:
                    break
                while self.next_k < tr.due_before(now - self.start):
                    self.submit(self.start + tr.request(self.next_k).due)
                if eng.poll():
                    self._step()
                else:
                    nxt = self.start + tr.request(self.next_k).due
                    with self.span("idle"):
                        time.sleep(max(0.0, min(nxt, self.start + seconds)
                                       - eng.now()))
        self.end = eng.now()
        self.stats1 = snapshot(eng.stats)


def snapshot(stats: dict) -> dict:
    """Counters and the lengths of the per-step lists of ``engine.stats``."""
    out = {}
    for k, v in stats.items():
        out[k] = len(v) if isinstance(v, list) else v
    return out


def sample_finished(recs: dict, seed: int, n: int) -> list[Rec]:
    """``n`` finished requests drawn from the seed, the one with the most
    served tokens always among them."""
    done = sorted((r for r in recs.values() if r.done and r.tokens),
                  key=lambda r: r.k)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.k))
    rest = [r for r in done if r is not longest]
    rng = random.Random(int(seed) ^ 0x5A3F1E)
    return [longest] + rng.sample(rest, min(n - 1, len(rest)))
