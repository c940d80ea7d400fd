"""Reduce a profiler trace to device busy time, per-kernel and per-module
device time, and idle gaps labelled by the harness's host spans.

A trace is read into plain ``Event`` records (``load``), so the reduction
itself (``reduce``) runs on any list of events, recorded or made up.
Device events are those on planes named ``/device:TPU:<n>``; on those
planes the ``XLA Ops`` line holds one event per executed operation (a
Pallas kernel is one operation named after its ``pallas_call``), and the
``XLA Modules`` line one event per executed jitted program (``jit__step``,
``jit__chunk``).  Host spans are ``TraceAnnotation`` events whose name
starts with ``bench.`` on the host plane.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    source: str = ""          # a device op's framework name (``tf_op``)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str | Path) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        keep_all = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            sources: dict[str, str] = {}    # op name -> its tf_op, read once
            for e in line.events:
                if keep_all or e.name.startswith(SPAN_PREFIX):
                    src = ""
                    if keep_all and line.name == OPS_LINE:
                        if e.name not in sources:
                            sources[e.name] = next(
                                (str(v) for k, v in e.stats
                                 if k in ("tf_op", "long_name")), "")
                        src = sources[e.name]
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns),
                                     src))
    return out


def union_ns(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The stretches of ``[lo, hi]`` that no interval covers, as
    ``(start, end)``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def clip(ev: Event, lo: float, hi: float):
    return max(ev.start_ns, lo), min(ev.end_ns, hi)


def op_name(name: str) -> str:
    """An op event's own name: a TPU trace names it by its HLO text,
    ``%paged_attention.78 = f32[...] custom-call(...)``, whose name is
    ``paged_attention.78``."""
    return name.split(" = ")[0].strip().lstrip("%")


def kernel_of(name: str, kernels, source: str = "") -> str | None:
    """The kernel an op event belongs to: the one of ``kernels`` that the
    event is named after (``rht_qmatmul.12``), or whose ``pallas_call`` its
    framework name holds (``jit(_step)/.../rht_qmatmul/pallas_call``)."""
    base = op_name(name).split(".")[0].split(":")[0]
    for k in kernels:
        if base == k or f"/{k}/pallas_call" in source:
            return k
    return None


def op_kind(name: str) -> str:
    """An op's name without its instance number: ``fusion.12`` -> fusion."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def module_of(name: str) -> str:
    """``jit__step(12)`` -> ``jit__step``."""
    return name.split("(")[0].strip()


@dataclasses.dataclass
class Reduced:
    window_ns: float          # length of the traced window
    busy_ns: float            # device busy, averaged over the chips
    chips: int
    kernel_ns: dict           # kernel -> summed device time, all chips / n
    kernel_calls: dict        # kernel -> events, all chips / n
    module_ns: dict           # jitted program -> summed device time / n
    module_calls: dict
    top_ops: list             # [(op kind, seconds)], most time first
    idle_gaps: list           # [(host span label, seconds)], longest first


def reduce(events: list[Event], kernels=("rht_qmatmul", "paged_attention"),
           window_span: str = "bench.window", top: int = 10) -> Reduced:
    """Reduce ``events`` over the host span ``window_span`` (or, without
    one, the extent of the device events)."""
    spans = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    dev = [e for e in events if DEVICE_PLANE.match(e.plane)]
    win = [e for e in spans if e.name == window_span]
    if win:
        lo = min(e.start_ns for e in win)
        hi = max(e.end_ns for e in win)
    elif dev:
        lo = min(e.start_ns for e in dev)
        hi = max(e.end_ns for e in dev)
    else:
        raise ValueError("the trace holds no device events and no window")
    if not dev:
        return Reduced(hi - lo, 0.0, 0, {}, {}, {}, {}, [], [])
    chips = sorted({e.plane for e in dev})
    n = max(len(chips), 1)
    busy, k_ns, k_calls, m_ns, m_calls, op_ns = 0.0, {}, {}, {}, {}, {}
    idle = []
    inner = sorted((e for e in spans if e.name != window_span
                    and e.end_ns > lo and e.start_ns < hi),
                   key=lambda e: e.start_ns)
    for chip in chips:
        ops = [e for e in dev if e.plane == chip and e.line == OPS_LINE
               and e.end_ns > lo and e.start_ns < hi]
        iv = [clip(e, lo, hi) for e in ops]
        busy += union_ns(iv)
        for e in ops:
            s, t = clip(e, lo, hi)
            op = op_kind(e.name)
            op_ns[op] = op_ns.get(op, 0.0) + (t - s)
            k = kernel_of(e.name, kernels, e.source)
            if k:
                k_ns[k] = k_ns.get(k, 0.0) + (t - s)
                k_calls[k] = k_calls.get(k, 0) + 1
        for e in dev:
            if (e.plane == chip and e.line == MODULES_LINE
                    and e.end_ns > lo and e.start_ns < hi):
                s, t = clip(e, lo, hi)
                m = module_of(e.name)
                m_ns[m] = m_ns.get(m, 0.0) + (t - s)
                m_calls[m] = m_calls.get(m, 0) + 1
        for g0, g1 in gaps(iv, lo, hi):
            idle.append((span_at(inner, (g0 + g1) / 2), (g1 - g0) / n))
    scale = lambda d: {k: v / n for k, v in d.items()}   # noqa: E731
    by_label: dict[str, float] = {}
    for label, s in idle:
        by_label[label] = by_label.get(label, 0.0) + s
    longest = sorted(idle, key=lambda x: -x[1])[:top]
    return Reduced(
        window_ns=hi - lo, busy_ns=busy / n, chips=n,
        kernel_ns=scale(k_ns), kernel_calls=scale(k_calls),
        module_ns=scale(m_ns), module_calls=scale(m_calls),
        top_ops=[(k, v / n / 1e9) for k, v in
                 sorted(op_ns.items(), key=lambda x: -x[1])[:top]],
        idle_gaps=[(lab, s / 1e9) for lab, s in longest])


def span_at(spans, t: float) -> str:
    """The host span covering time ``t`` that started last (the harness's
    spans inside the window follow one another), by name without the
    ``bench.`` prefix; ``"none"`` where no span covers it.  ``spans`` is
    sorted by start."""
    i = bisect.bisect_right([e.start_ns for e in spans], t) - 1
    while i >= 0:
        if spans[i].end_ns >= t:
            return spans[i].name[len(SPAN_PREFIX):]
        i -= 1
        if i >= 0 and t - spans[i].start_ns > 60e9:
            break
    return "none"


def summary(events: list[Event], top: int = 8) -> dict:
    """Planes, lines, event counts and the commonest names on each line:
    what a reader checks by hand before trusting the reduction."""
    out: dict = {}
    for e in events:
        line = out.setdefault(e.plane, {}).setdefault(
            e.line, {"events": 0, "names": {}})
        line["events"] += 1
        line["names"][e.name] = line["names"].get(e.name, 0) + 1
    for lines in out.values():
        for line in lines.values():
            line["names"] = sorted(line["names"].items(),
                                   key=lambda x: -x[1])[:top]
    return out
