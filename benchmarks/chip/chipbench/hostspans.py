"""Device-idle time charged to the serving engine's own spans.

The engine wraps each boundary of an iteration in a profiler annotation
named ``serve.<name>`` (``repro/serve/tracing.py``): ``step``, ``admit``,
``cow``, ``prefill``, ``decode`` and ``spec``, the last three split into
``.inputs``, ``.dispatch``, ``.pull`` and ``.sample``.  Those spans share
the trace's clock with the device's operations.  Each stretch of the
window in which no operation ran on a chip is charged to the innermost
``serve.*`` span covering it, and falls into one of four parts that
together make the device's idle time (``tracefile.reduce``'s ``busy_ns``):

- ``pull``: under a ``*.pull`` span, the host waiting for logits;
- ``sample``: under a ``*.sample`` span, host sampling and bookkeeping;
- ``dispatch``: under any other ``serve.*`` span (admission, copy on
  write, building inputs, dispatching a program, or a parent's own time);
- ``outside``: under no ``serve.*`` span (the harness's load generator and
  record, Python between engine calls).

A program without these spans yields nothing: every share is ``None``.
"""
from __future__ import annotations

from .tracefile import OPS_LINE, device_planes, gaps

PREFIX = "serve."
WINDOW = "bench.window"
PARTS = ("pull", "sample", "dispatch", "outside")


def part_of(span: str | None) -> str:
    """The part an idle stretch under innermost span ``span`` (without its
    prefix; ``None`` for no span) belongs to."""
    if span is None:
        return "outside"
    for part in ("pull", "sample"):
        if span.endswith("." + part):
            return part
    return "dispatch"


def segments(spans, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into ``(start, end, name)`` pieces, ``name`` the
    innermost of ``spans`` (``(start, end, name)``) covering the piece, or
    ``None``.  A span that outlasts the one it starts in is cut at that
    one's end, so the spans nest as they do on one thread."""
    out, stack, t = [], [], lo

    def close(upto):
        nonlocal t
        upto = min(upto, hi)
        if upto > t:
            out.append((t, upto, stack[-1][1] if stack else None))
            t = upto

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack[-1][0])
            stack.pop()
        close(s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        close(stack[-1][0])
        stack.pop()
    close(hi)
    return out


def charge(idle, segs) -> dict:
    """Nanoseconds of the ``(start, end)`` stretches of ``idle`` (sorted,
    disjoint) under each innermost span of ``segs`` (``segments``)."""
    out: dict = {}
    j = 0
    for g0, g1 in idle:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b = max(g0, segs[k][0]), min(g1, segs[k][1])
            if b > a:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + b - a
            k += 1
    return out


def split(events: list) -> dict | None:
    """Each part's share of the window (%), idle time averaged over the
    chips as ``tracefile.reduce`` averages busy time, and the idle
    milliseconds under each innermost span; ``None`` without a window,
    device operations or ``serve.*`` spans.  ``events`` are
    ``tracefile.load``'s: the device's operations are those on its
    ``XLA Ops`` line."""
    device = device_planes(events)
    host = [e for e in events if e.plane not in device]
    win = [e for e in host if e.name == WINDOW]
    spans = [(e.start_ns, e.end_ns, e.name[len(PREFIX):]) for e in host
             if e.name.startswith(PREFIX)]
    dev = [e for e in events if e.plane in device and e.line == OPS_LINE]
    if not win or not spans or not dev:
        return None
    lo = min(e.start_ns for e in win)
    hi = max(e.end_ns for e in win)
    segs = segments(spans, lo, hi)
    chips = sorted({e.plane for e in dev})
    ivs: dict = {c: [] for c in chips}
    for e in dev:
        if e.end_ns > lo and e.start_ns < hi:
            ivs[e.plane].append((max(e.start_ns, lo), min(e.end_ns, hi)))
    by_span: dict = {}
    for chip in chips:
        for name, ns in charge(gaps(ivs[chip], lo, hi), segs).items():
            by_span[name] = by_span.get(name, 0.0) + ns
    scale = len(chips) * (hi - lo)
    share = dict.fromkeys(PARTS, 0.0)
    for name, ns in by_span.items():
        share[part_of(name)] += 100.0 * ns / scale
    return {"share": share,
            "span_ms": {name or "outside": ns / len(chips) / 1e6
                        for name, ns in sorted(by_span.items(),
                                               key=lambda x: -x[1])}}


def idle_share(ctx, part: str):
    """Share of the traced window (%) in which the device idled under
    ``part``, from the run's events (``ctx.events``); split once per run
    and kept on ``ctx``."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "serve_idle"):
        ctx.serve_idle = split(ctx.events)
        if ctx.serve_idle is not None:
            ctx.note("device idle under serve spans (ms)",
                     ctx.serve_idle["span_ms"])
    return None if ctx.serve_idle is None else ctx.serve_idle["share"][part]
