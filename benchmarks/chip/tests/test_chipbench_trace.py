"""The trace reduction, on synthetic events and on a trace recorded here."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import tracefile as tf  # noqa: E402
from chipbench.cell import Ctx, reader  # noqa: E402
from chipbench.tracefile import Reduced  # noqa: E402

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


def test_prefill_chunk_ms_is_device_time_per_chunk():
    """``jit__chunk`` executions in the window, the part of one that
    outlives it clipped, over their count; a window without a chunk, or an
    untraced run, reads nothing."""
    events = synthetic() + [
        ev("jit__chunk(7)", 520, 60, line=tf.MODULES_LINE),
        ev("jit__chunk(7)", 960, 80, line=tf.MODULES_LINE),
        ev("jit__chunk(7)", 1200, 90, line=tf.MODULES_LINE)]
    read = reader("prefill_chunk_ms.longchat")
    assert read(Ctx(trace=tf.reduce(events))) == pytest.approx(
        (60 + 40) / 2 / 1e6)
    assert read(Ctx(trace=tf.reduce(synthetic()))) is None
    assert read(Ctx(trace=None)) is None


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


# What the two-pass reading (``tracefile.load`` and ``reduce``, then
# ``hostspans.load`` and ``split`` on a second read of the file) gave on
# ``tpu_xspace``'s trace, and every per-layer reader of the batch cell on
# ``read_once_ctx``: the one read must give each of them unchanged.
TWO_PASS_REDUCED = Reduced(
    window_ns=328410.0, busy_ns=219028.0, chips=2,
    kernel_ns={"rht_qmatmul": 67297.5, "paged_attention": 21236.0},
    kernel_calls={"rht_qmatmul": 334.0, "paged_attention": 104.0},
    module_ns={"jit__chunk": 98163.5, "jit__step": 130245.0},
    module_calls={"jit__chunk": 15.5, "jit__step": 21.5},
    top_ops=[("fusion", 6.41735e-05), ("rht_qmatmul", 5.64195e-05),
             ("bitcast_dynamic-update-slice_fusion", 3.42e-05),
             ("copy", 3.35685e-05), ("custom-call", 3.2114e-05)],
    idle_gaps=[("step", 8.684e-06), ("step", 8.5585e-06),
               ("none", 8.0155e-06), ("step", 7.0965e-06),
               ("step", 5.6345e-06), ("step", 4.8845e-06),
               ("step", 3.885e-06), ("step", 3.6665e-06),
               ("step", 3.125e-06), ("step", 3.036e-06)])
TWO_PASS_SPLIT = {
    "share": {"pull": 17.094028805456595, "sample": 6.691026460826406,
              "dispatch": 3.623519381261228, "outside": 5.897962912213392},
    "span_ms": {"prefill.pull": 0.0299515, "decode.pull": 0.026187,
                "outside": 0.0193695, "decode.sample": 0.0139045,
                "prefill.sample": 0.0080695, "admit": 0.004584,
                "decode.inputs": 0.002815, "prefill.inputs": 0.002093,
                "decode.dispatch": 0.001307, "prefill.dispatch": 0.000851,
                "cow": 0.00025}}
TWO_PASS_READINGS = {
    "calibrate_s": 1.25, "occupancy.batch": 47.22156045376933,
    "decode_step_ms.batch": 0.0060579069767441865,
    "decode_mfu.batch": 17385.487805227673,
    "rht_qmatmul_roofline.batch": 1004.022659032132,
    "paged_attention_roofline.batch": 344442.72874109,
    "device_idle.batch": 33.30653755975762,
    "queue_wait_ms.batch": 87.83783783783784,
    "prefill_wait_ms.batch": 256.94444444444446,
    "prefill_slots.batch": 11.484375,
    "idle_pull.batch": 17.094028805456595,
    "idle_sample.batch": 6.691026460826406,
    "idle_dispatch.batch": 3.623519381261228,
    "idle_outside.batch": 5.897962912213392,
    "prefill_chunk_ms.batch": 0.006333129032258064}


def read_once_ctx(red, events):
    """A batch run's record made up from a fixed seed: decode steps of 40
    to 64 slots, some with a 32-token chunk, three matrices."""
    import random
    from types import SimpleNamespace

    from chipbench import work
    from chipbench.session import StepRec
    model = json.loads((Path(__file__).resolve().parents[1] / "configs"
                        / "internlm2-1.8b.json").read_text())["model"]
    rng = random.Random(3)
    steps = [StepRec(end=float(i), chunk=rng.choice([0, 0, 32]),
                     contexts=[rng.randint(33, 896)
                               for _ in range(rng.randint(40, 64))])
             for i in range(25)]
    qm = [work.QMatrix(2042, 6144, 3, 1024, True),
          work.QMatrix(2048, 2048, 4, 2048, False),
          work.QMatrix(8168, 2048, 3, 4096, True)]
    occ = [rng.random() for _ in range(60)]
    s0 = {"occupancy": 10, "admitted": 3, "queue_wait_s": 0.5,
          "prefill_started": 2, "prefill_wait_s": 0.25,
          "prefill_slot_steps": 7, "decode_steps": 11}
    s1 = {"occupancy": 50, "admitted": 40, "queue_wait_s": 3.75,
          "prefill_started": 38, "prefill_wait_s": 9.5,
          "prefill_slot_steps": 301, "decode_steps": 51}
    return Ctx(model=model, win=SimpleNamespace(start=0.0, end=30.0,
                                                stats0=s0, stats1=s1),
               steps=steps, stats={"occupancy": occ},
               times={"calibrate_s": 1.25}, trace=red, events=events,
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
               qmatrices=qm, experts=(), slots=64, block_size=16,
               kv_bytes=2)


def test_one_read_gives_the_two_pass_readings(tmp_path):
    """A TPU-shaped trace written as an ``.xplane.pb`` (``tpu_xspace``),
    read once: the reduction, the idle split and every per-layer reading
    of the batch cell equal the two-pass code's, digit for digit."""
    import tpu_xspace
    from chipbench import hostspans as hs
    from chipbench.cell import metric_names
    tpu_xspace.write(tmp_path)
    events = tf.load(tmp_path)
    assert {e.name for e in events if e.name.startswith("serve.")}
    red = tf.reduce(events)
    assert red == TWO_PASS_REDUCED
    assert hs.split(events) == TWO_PASS_SPLIT
    ctx = read_once_ctx(red, events)
    bench = json.loads((Path(__file__).resolve().parents[3]
                        / "BENCHMARK.json").read_text())
    names = metric_names(bench, "internlm2-1.8b.batch", True)
    assert {n: reader(n)(ctx) for n in names} == TWO_PASS_READINGS
