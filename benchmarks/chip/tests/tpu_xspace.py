"""A small trace shaped as the profiler writes one on a TPU, built as an
``XSpace`` text proto from a fixed seed and written as an ``.xplane.pb``:
a host plane with the harness's ``bench.*`` spans, the engine's
``serve.*`` spans and Python noise, and device planes whose ``XLA Ops``
line holds operations named by their HLO text (kernels among them, some
found only through their ``tf_op``), whose ``XLA Modules`` line holds the
step and chunk programs, and whose other lines a reduction must ignore.
Iterations straddle both ends of the window."""
from __future__ import annotations

import random
from pathlib import Path

TF_OP = 1
LONG_NAME = 2


class _Plane:
    def __init__(self, pid: int, name: str):
        self.pid, self.name = pid, name
        self.meta: dict[str, int] = {}
        self.lines: dict[str, list] = {}

    def add(self, line: str, name: str, start: int, dur: int, stat=None):
        mid = self.meta.setdefault(name, len(self.meta) + 1)
        self.lines.setdefault(line, []).append((mid, start, dur, stat))

    def text(self) -> str:
        out = [f'planes {{ id: {self.pid} name: "{self.name}"']
        for lid, (line, evs) in enumerate(self.lines.items(), 1):
            out.append(f'  lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
            for mid, s, d, stat in evs:
                st = ""
                if stat:
                    st = (f' stats {{ metadata_id: {stat[0]} '
                          f'str_value: "{stat[1]}" }}')
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{s * 1000} duration_ps: {d * 1000}{st} }}")
            out.append("  }")
        for name, mid in self.meta.items():
            esc = name.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "{esc}" }} }}')
        out.append(f'  stat_metadata {{ key: {TF_OP} value {{ id: {TF_OP} '
                   f'name: "tf_op" }} }}')
        out.append(f'  stat_metadata {{ key: {LONG_NAME} value {{ id: '
                   f'{LONG_NAME} name: "long_name" }} }}')
        out.append("}")
        return "\n".join(out)


def _ops(rng, dev: _Plane, program: str, t: int, end: int) -> None:
    """Operations of one execution of ``program`` in ``[t, end]``, some
    overlapping, with gaps between a few."""
    k = 0
    while t < end - 40:
        k += 1
        d = rng.randint(20, 400)
        kind = rng.random()
        if kind < 0.25:
            dev.add("XLA Ops", f"%rht_qmatmul.{rng.randint(1, 9)} = "
                    f"f32[64,2048]{{1,0}} custom-call(f32[64,2048]{{1,0}} "
                    f"%p.{k})", t, d)
        elif kind < 0.35:
            dev.add("XLA Ops", f"%custom-call.{rng.randint(1, 5)} = "
                    f"f32[64,8,2,128]{{3,2,1,0}} custom-call(s32[64,56]"
                    f"{{1,0}} %copy-done.{k})", t, d,
                    (TF_OP, f"jit({program})/while/body/paged_attention/"
                     "pallas_call"))
        elif kind < 0.40:
            dev.add("XLA Ops", f"%custom-call.{rng.randint(6, 8)} = "
                    "f32[32,6144]{1,0} custom-call()", t, d,
                    (LONG_NAME, f"jit({program})/rht_qmatmul/pallas_call"))
        elif kind < 0.70:
            dev.add("XLA Ops", f"%fusion.{rng.randint(1, 30)} = "
                    f"bf16[24,2800,8,16,128]{{4,3,2,1,0}} fusion(%p.{k})",
                    t, d, (TF_OP, f"jit({program})/add"))
        elif kind < 0.85:
            dev.add("XLA Ops", f"%copy.{rng.randint(1, 12)} = "
                    "bf16[2800,8,16,128]{3,2,1,0} copy(%bitcast.3)", t, d)
        else:
            dev.add("XLA Ops", f"%bitcast_dynamic-update-slice_fusion."
                    f"{rng.randint(1, 4)} = bf16[24,2800]{{1,0}} fusion()",
                    t, d)
        t += d - rng.randint(0, 15) if rng.random() < 0.2 else \
            d + rng.randint(0, 30)


def xspace_text(seed: int = 7, iterations: int = 30, chips: int = 2) -> str:
    rng = random.Random(seed)
    host = _Plane(1, "/host:CPU")
    devs = [_Plane(2 + c, f"/device:TPU:{c}") for c in range(chips)]
    t = 0
    host.add("python", "bench.warm", 0, 500)
    win0 = 2_000
    for it in range(iterations):
        step0 = t = t + rng.randint(100, 600)
        a = rng.randint(50, 300)
        host.add("python", "serve.admit", t, a)
        if rng.random() < 0.3:
            host.add("python", "serve.cow", t + 10, a // 2)
        t += a
        for phase, program in (("prefill", "_chunk"), ("decode", "_step")):
            if phase == "prefill" and rng.random() < 0.4:
                continue
            p0 = t
            i = rng.randint(30, 200)
            host.add("python", f"serve.{phase}.inputs", t, i)
            t += i
            d = rng.randint(20, 120)
            host.add("python", f"serve.{phase}.dispatch", t, d)
            run0 = t + d // 2
            length = rng.randint(3_000, 9_000)
            for c, dev in enumerate(devs):
                if c and rng.random() < 0.3:
                    continue                            # a chip sits out
                skew = rng.randint(0, 50) * c
                dev.add("XLA Modules", f"jit_{program}({it % 7})",
                        run0 + skew, length)
                dev.add("Steps", str(it), run0 + skew, length)
                _ops(rng, dev, program, run0 + skew, run0 + skew + length)
            t += d
            pull = run0 + length + rng.randint(10, 200) - t
            host.add("python", f"serve.{phase}.pull", t, pull)
            t += pull
            s = rng.randint(100, 900)
            host.add("python", f"serve.{phase}.sample", t, s)
            t += s
            host.add("python", f"serve.{phase}", p0, t - p0)
            host.add("python", "$builtins len", p0 + 3, 2)
        host.add("python", "serve.step", step0, t - step0)
        host.add("python", "bench.step", step0, t - step0)
        r = rng.randint(50, 500)
        host.add("python", "bench.record", t, r)
        t += r
        if rng.random() < 0.5:
            g = rng.randint(20, 80)
            host.add("python", "bench.generator", t, g)
            host.add("python", "bench.submit", t + g, g)
            t += 2 * g
        host.add("tf_XLAEigen/1", "ThunkExecutor::Execute", t - 40, 30)
        if it == 2:
            win0 = t - 1_000
        if it == iterations - 3:
            win1 = t + 500
    host.add("python", "bench.window", win0, win1 - win0)
    return "\n".join(p.text() for p in [host] + devs)


def write(trace_dir: Path, seed: int = 7, **kw) -> Path:
    """The trace as ``<trace_dir>/plugins/profile/x/vm.xplane.pb``."""
    from jax.profiler import ProfileData
    path = Path(trace_dir) / "plugins" / "profile" / "x" / "vm.xplane.pb"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        xspace_text(seed, **kw)))
    return path
