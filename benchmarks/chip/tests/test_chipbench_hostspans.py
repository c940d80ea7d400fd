"""Device-idle time charged to the engine's ``serve.*`` spans
(``chipbench/hostspans.py``), on made-up events and on a trace recorded
here, and the readers of the engine's wait counters."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import cell, hostspans as hs  # noqa: E402
from chipbench import tracefile as tf  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
IDLE = ["idle_pull", "idle_sample", "idle_dispatch", "idle_outside"]
COUNTED = ["queue_wait_ms", "prefill_wait_ms", "prefill_slots"]


def ev(name, s, d, plane=HOST, line="python"):
    return tf.Event(plane, line, name, float(s), float(d))


def op(s, d, plane=DEV):
    return ev("fusion.1", s, d, plane, tf.OPS_LINE)


def iteration():
    """One engine iteration in a window of 1000 ns.  Device idle:
    [0, 60] under admit (50) and step's own time (10), [520, 700] under
    decode.pull, [700, 800] under decode.sample, [800, 900] under step,
    [900, 950] under no span."""
    return [
        ev("bench.window", 0, 1000),
        ev("bench.step", 0, 900),
        ev("serve.step", 0, 900),
        ev("serve.admit", 0, 50),
        ev("serve.decode", 100, 700),
        ev("serve.decode.dispatch", 100, 50),
        ev("serve.decode.pull", 500, 200),
        ev("serve.decode.sample", 700, 100),
        op(60, 460), op(950, 50),
    ]


def shares(events):
    return hs.split(events)["share"]


def test_gap_under_pull_counts_as_pull():
    assert shares(iteration())["pull"] == pytest.approx(18.0)
    assert shares(iteration())["sample"] == pytest.approx(10.0)


def test_innermost_span_wins():
    """Idle under decode.pull is pull, though decode and step cover it
    too; idle under step alone is the step's own time, dispatch."""
    got = hs.split(iteration())
    assert got["share"]["dispatch"] == pytest.approx(5.0 + 1.0 + 10.0)
    assert got["span_ms"]["decode.pull"] == pytest.approx(180e-6)
    assert got["span_ms"]["admit"] == pytest.approx(50e-6)
    assert got["span_ms"]["step"] == pytest.approx(110e-6)
    assert "decode" not in got["span_ms"]


def test_gap_under_no_serve_span_is_outside():
    # bench.* spans are the harness's, not the engine's
    assert shares(iteration())["outside"] == pytest.approx(5.0)


@pytest.mark.parametrize("chips", [1, 2])
def test_parts_sum_to_device_idle(chips):
    events = iteration()
    if chips == 2:
        events += [op(0, 300, "/device:TPU:1"), op(700, 120, "/device:TPU:1")]
    red = tf.reduce(events)
    idle = 100.0 * (1.0 - red.busy_ns / red.window_ns)
    assert sum(shares(events).values()) == pytest.approx(idle)
    assert cell.reader("device_idle.batch")(
        SimpleNamespace(trace=red)) == pytest.approx(idle)


def test_spans_outliving_the_window_or_their_parent():
    events = [ev("bench.window", 100, 900), ev("serve.step", 0, 2000),
              ev("serve.decode", 50, 500),
              ev("serve.decode.pull", 400, 400),   # outlives decode
              op(800, 100)]
    got = hs.split(events)["span_ms"]
    # [100, 800] idle: decode.pull [400, 550], decode [100, 400], step after
    assert got == pytest.approx({"decode": 300e-6, "decode.pull": 150e-6,
                                 "step": 250e-6 + 100e-6})


def test_without_serve_spans_or_device_ops_nothing_is_split():
    plain = [e for e in iteration() if not e.name.startswith("serve.")]
    assert hs.split(plain) is None
    assert hs.split([e for e in iteration()
                      if not e.plane.startswith("/device")]) is None
    assert hs.split([e for e in iteration()
                     if e.name != "bench.window"]) is None


def ctx(stats0=None, stats1=None, trace=None):
    return cell.Ctx(win=SimpleNamespace(stats0=stats0 or {},
                                        stats1=stats1 or {}),
                    trace=trace, slots=4, stats={})


@pytest.mark.parametrize("name", IDLE)
def test_idle_readers(name):
    part = name.split("_")[1]
    read = cell.reader(f"{name}.batch")
    assert read(ctx()) is None                      # untraced
    c = ctx(trace=tf.reduce(iteration()))
    c.events = [e for e in iteration() if not e.name.startswith("serve.")]
    assert hs.idle_share(c, part) is None           # no serve spans
    c = ctx(trace=tf.reduce(iteration()))
    c.events = iteration()
    assert read(c) == pytest.approx(shares(iteration())[part])
    c = ctx(trace=tf.reduce(iteration()))
    c.serve_idle = hs.split(iteration())
    assert read(c) == pytest.approx(shares(iteration())[part])
    c = ctx(trace=tf.reduce(iteration()))
    c.serve_idle = hs.split([e for e in iteration()
                             if not e.name.startswith("serve.")])
    assert read(c) is None                          # a program without spans


def test_counter_readers():
    s0 = {"admitted": 10, "queue_wait_s": 1.0, "prefill_started": 8,
          "prefill_wait_s": 4.0, "prefill_slot_steps": 30,
          "decode_steps": 100}
    s1 = {"admitted": 14, "queue_wait_s": 1.2, "prefill_started": 12,
          "prefill_wait_s": 12.0, "prefill_slot_steps": 40,
          "decode_steps": 110}
    c = ctx(s0, s1)
    assert cell.reader("queue_wait_ms.batch")(c) == pytest.approx(50.0)
    assert cell.reader("prefill_wait_ms.batch")(c) == pytest.approx(2000.0)
    assert cell.reader("prefill_slots.batch")(c) == pytest.approx(25.0)
    # a program without the counters: decode steps alone read nothing
    c = ctx({"decode_steps": 100}, {"decode_steps": 110})
    for name in COUNTED:
        assert cell.reader(f"{name}.batch")(c) is None


def test_load_reads_serve_spans_of_a_recorded_trace(tmp_path):
    """The one read of a trace (``tracefile.load``) keeps the engine's
    spans beside the harness's."""
    import jax
    import jax.numpy as jnp
    from repro.serve.tracing import span
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    stats: dict = {}
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with span(stats, "step"):
            with span(stats, "decode", slots=3):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tf.load(tmp_path)
    names = sorted(e.name for e in events
                   if not tf.DEVICE_PLANE.match(e.plane))
    assert names == ["bench.window", "serve.decode", "serve.step"]
    assert stats["span_n.step"] == stats["span_n.decode"] == 1
    assert stats["span_s.step"] >= stats["span_s.decode"] > 0.0


def test_traced_tiny_run_reads_the_counters():
    """A traced run through the harness at the tiny size: the counter
    metrics read, and with ``occupancy`` they account for the held slots.
    A CPU trace has no TPU plane, so the idle parts read nothing, as
    ``device_idle`` reads nothing."""
    from test_chipbench_run import CELLS, SEED, TINY, load_bench, tiny_conf
    res = cell.run_cell(load_bench(), CELLS["batch"], SEED, 1.0, True,
                        conf=tiny_conf("batch"), mix=TINY["mixes"]["batch"],
                        peaks=TINY["peaks"])
    assert res["correct"], res["check"]
    got = {k.split(".")[0]: v["value"] for k, v in res["metrics"].items()}
    assert set(COUNTED) <= set(got)
    assert not set(IDLE + ["device_idle"]) & set(got)
    assert 0.0 <= got["queue_wait_ms"] and 0.0 <= got["prefill_wait_ms"]
    assert 0.0 < got["prefill_slots"]
    assert got["prefill_slots"] + got["occupancy"] <= 100.0 + 1e-9
