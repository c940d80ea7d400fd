"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

Every size and gap is drawn as a fixed set of quantiles of the mix's
distribution, put in an order drawn from the seed.  So every seed offers the
same work in another order, and two seeds differ in timing only as much as
the order makes them; token ids are uniform over the vocabulary from the
seed.

Mix keys:

* ``loop``: ``"closed"`` (``clients`` each send their next request when the
  last one finishes) or ``"open"`` (``arrivals`` with ``rate_per_s``;
  request ``k`` is due at the sum of the first ``k + 1`` exponential gaps).
* ``prompt``, ``output``: length distributions, ``{"dist": "uniform", "lo",
  "hi"}`` or ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}``
  (clipped to ``[lo, hi]``), each optionally with ``"step"`` and
  ``"offset"``: lengths are then the nearest of ``offset + step * i`` in
  ``[lo, hi]``.  The serving program compiles one prefill program per
  chunk length, so a step keeps a run's set of lengths, and its set-up,
  small (``chunk_lengths``).
* ``pool`` (optional, default 4096): quantiles per distribution.  A mix
  whose window serves few requests sets it near that number, so that every
  seed's window holds the same sizes and gaps and only their order moves
  with the seed.
* ``sessions`` (optional): ``{"count", "history": <length distribution>}``.
  Set-up prefills each session's history once; every request then picks a
  session (each equally often) and asks about it with a new turn of
  ``prompt`` tokens after the history.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

POOL = 4096          # quantiles per distribution


def quantile_pool(spec: dict, n: int = POOL) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of ``spec``, as ints."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["lo"] + u * (spec["hi"] + 1 - spec["lo"])
        x = np.floor(x)
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(x, spec["lo"], spec["hi"])
    step, off = spec.get("step", 1), spec.get("offset", 0)
    lo = off + step * math.ceil((spec["lo"] - off) / step)
    hi = off + step * math.floor((spec["hi"] - off) / step)
    x = off + step * np.round((x - off) / step)
    return np.clip(x, lo, hi).astype(np.int64)


def exp_gaps(rate: float, n: int = POOL) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (s) at the quantiles of
    Exp(rate); their mean is 1/rate to within 1/n."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Req:
    """One request as the generator offers it."""
    k: int                    # index in the mix's sequence
    prompt: np.ndarray        # int32 token ids
    max_new: int
    due: float = 0.0          # open loop: seconds after the window opens
    session: int = -1


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {self.loop}")
        rng = np.random.default_rng([int(seed), 0x7A11C])
        n = int(mix.get("pool", POOL))

        def cycle(values):
            """Fresh orders of ``values`` one after another: every run of
            ``n`` requests from the start holds the same sizes."""
            return np.concatenate([rng.permutation(values)
                                   for _ in range(-(-POOL // n))])[:POOL]

        self._prompt = cycle(quantile_pool(mix["prompt"], n))
        self._output = cycle(quantile_pool(mix["output"], n))
        self._tok_seed = int(seed)
        self.histories: list[np.ndarray] = []
        if "sessions" in mix:
            s = mix["sessions"]
            lens = rng.permutation(quantile_pool(s["history"], s["count"]))
            self.histories = [self._tokens(("history", i), int(h))
                              for i, h in enumerate(lens)]
            reps = -(-POOL // s["count"])
            self._session = np.concatenate(
                [rng.permutation(s["count"]) for _ in range(reps)])
        if self.loop == "open":
            self._due = np.cumsum(cycle(
                exp_gaps(mix["arrivals"]["rate_per_s"], n)))

    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    def _tokens(self, tag, n: int) -> np.ndarray:
        tag = tag if isinstance(tag, tuple) else (tag,)
        key = [self._tok_seed] + [hash_int(t) for t in tag]
        return np.random.default_rng(key).integers(
            0, self.vocab, n).astype(np.int32)

    def request(self, k: int) -> Req:
        """The ``k``-th request of the mix (the same for the same seed)."""
        i = k % POOL
        turn = self._tokens(("turn", k), int(self._prompt[i]))
        r = Req(k=k, prompt=turn, max_new=int(self._output[i]))
        if self.histories:
            r.session = int(self._session[k % len(self._session)])
            r.prompt = np.concatenate([self.histories[r.session], turn])
        if self.loop == "open":
            if k >= POOL:
                raise IndexError(f"an open-loop mix holds {POOL} requests")
            r.due = float(self._due[k])
        return r

    def due_before(self, t: float) -> int:
        """Open loop: how many requests are due before ``t`` seconds."""
        return int(np.searchsorted(self._due, t, side="left"))

    def chunk_lengths(self, chunk: int, block: int) -> list[int]:
        """Every prefill-chunk length the mix can make the engine run: a
        prompt is prefilled from its first uncached token in chunks of
        ``chunk``, the last one shorter.  A session's history is
        prefilled whole in set-up; a turn after it starts at the end of
        the history's last whole block, or, once an earlier turn has
        cached the block the history ends in, at the end of the history
        (copy-on-write)."""
        turns = set(quantile_pool(self.mix["prompt"]).tolist())
        todo = set(turns)
        if self.histories:
            tails = {len(h) % block for h in self.histories}
            todo |= {len(h) for h in self.histories}
            todo |= {r + t for r in tails for t in turns}
        out = {n % chunk for n in todo if n % chunk}
        if max(todo) >= chunk:
            out.add(chunk)
        return sorted(out)

    def longest(self) -> int:
        """Tokens of the longest request the mix allows (history, turn and
        output at their upper bounds): the same for every seed."""
        hist = (self.mix["sessions"]["history"]["hi"]
                if "sessions" in self.mix else 0)
        return int(hist + self.mix["prompt"]["hi"] + self.mix["output"]["hi"])


def hash_int(tag) -> int:
    """A stable non-negative int for a str or int tag (``hash`` of a str
    changes from process to process)."""
    if isinstance(tag, int):
        return tag
    return int.from_bytes(str(tag).encode(), "little") % (2 ** 63)


def percentile(values, q: float, missing=()) -> float:
    """The ``q``-th percentile of ``values`` and ``missing`` together
    (linear interpolation, as numpy's default).  ``missing`` holds lower
    bounds of values never observed (a first token that had not come when
    the window closed): each counts as later than every observed value."""
    v = np.sort(np.asarray(values, np.float64))
    top = v[-1] if v.size else 0.0
    m = np.sort(np.maximum(np.asarray(missing, np.float64), top))
    v = np.concatenate([v, m])
    if v.size == 0:
        return math.nan
    pos = (v.size - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
