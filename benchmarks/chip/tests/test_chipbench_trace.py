"""The trace reduction, on synthetic events and on a trace recorded here."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import tracefile as tf  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(name, s, d, plane=DEV, line=tf.OPS_LINE):
    return tf.Event(plane, line, name, float(s), float(d))


def test_union_merges_overlaps_and_touching():
    assert tf.union_ns([(0, 10), (5, 15), (15, 20), (30, 40)]) == 30
    assert tf.union_ns([]) == 0


def test_gaps_cover_what_no_interval_covers():
    assert tf.gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]
    assert tf.gaps([(0, 100)], 0, 100) == []


def test_kernel_and_module_names():
    assert tf.kernel_of("rht_qmatmul.12", ("rht_qmatmul",)) == "rht_qmatmul"
    assert tf.kernel_of("rht_qmatmul", ("rht_qmatmul",)) == "rht_qmatmul"
    assert tf.kernel_of("qmatmul.3", ("rht_qmatmul",)) is None
    assert tf.kernel_of("custom-call.7", ("rht_qmatmul", "paged_attention"),
                        "jit(_step)/while/body/paged_attention/pallas_call"
                        ) == "paged_attention"
    assert tf.module_of("jit__step(42)") == "jit__step"
    assert tf.op_kind("fusion.12") == "fusion"
    assert tf.op_kind("rht_qmatmul") == "rht_qmatmul"
    # on a TPU an op event is named by its HLO text
    hlo = ("%paged_attention.78 = f32[16,8,2,128]{3,2,1,0} custom-call("
           "s32[16,270]{1,0} %copy-done.48), custom_call_target=\"tpu\"")
    assert tf.kernel_of(hlo, ("rht_qmatmul", "paged_attention")) == \
        "paged_attention"
    assert tf.op_kind("%copy.1852 = bf16[28,8]{1,0} copy(bf16[28,8]{0,1} "
                      "%bitcast.3)") == "copy"


def synthetic():
    return [
        ev("bench.window", 0, 1000, HOST, "python"),
        ev("bench.step", 0, 600, HOST, "python"),
        ev("bench.record", 600, 100, HOST, "python"),
        ev("bench.submit", 700, 300, HOST, "python"),
        ev("jit__step(1)", 100, 400, line=tf.MODULES_LINE),
        ev("rht_qmatmul.1", 100, 100),
        ev("rht_qmatmul.2", 150, 100),        # overlaps the first
        ev("paged_attention", 300, 150),
        ev("fusion.7", 450, 50),
        ev("copy.1", 800, 100),
        ev("fusion.8", 1100, 50),             # after the window
    ]


def test_reduce_busy_kernels_modules_idle():
    r = tf.reduce(synthetic())
    assert r.window_ns == 1000 and r.chips == 1
    # busy: [100, 250] + [300, 500] + [800, 900]
    assert r.busy_ns == 150 + 200 + 100
    assert r.kernel_ns == {"rht_qmatmul": 200, "paged_attention": 150}
    assert r.kernel_calls == {"rht_qmatmul": 2, "paged_attention": 1}
    assert r.module_ns == {"jit__step": 400}
    assert r.module_calls == {"jit__step": 1}
    # idle: [0,100] step, [250,300] step, [500,800] record+submit
    # (midpoint 650 -> record), [900,1000] submit
    labels = dict((lab, s) for lab, s in r.idle_gaps)
    assert r.idle_gaps[0] == ("record", pytest.approx(300e-9))
    assert labels["submit"] == pytest.approx(100e-9)
    assert sorted(s for _, s in r.idle_gaps) == pytest.approx(
        [50e-9, 100e-9, 100e-9, 300e-9])
    assert r.top_ops[0] == ("rht_qmatmul", pytest.approx(200e-9))
    assert dict(r.top_ops)["fusion"] == pytest.approx(50e-9)


def test_reduce_averages_over_chips():
    events = synthetic() + [ev("paged_attention", 300, 150,
                               plane="/device:TPU:1")]
    r = tf.reduce(events)
    assert r.chips == 2
    assert r.busy_ns == (450 + 150) / 2
    assert r.kernel_ns["paged_attention"] == 150


def test_reduce_without_device_events_reads_nothing():
    r = tf.reduce([ev("bench.window", 0, 10, HOST, "python")])
    assert r.busy_ns == 0 and r.kernel_ns == {} and r.window_ns == 10


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tf.load(tmp_path)
    names = {e.name for e in events}
    assert {"bench.window", "bench.step"} <= names
    win = [e for e in events if e.name == "bench.window"][0]
    step = [e for e in events if e.name == "bench.step"][0]
    assert win.start_ns <= step.start_ns and step.end_ns <= win.end_ns
    assert tf.reduce(events).window_ns == pytest.approx(win.dur_ns)
    assert "bench.window" in str(tf.summary(events))
