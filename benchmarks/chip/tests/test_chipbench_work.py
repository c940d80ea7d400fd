"""Work counts against hand-computed shapes, the peaks table, and the
roofline and MFU readers' arithmetic."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import device, work  # noqa: E402
from chipbench.cell import reader  # noqa: E402
from chipbench.session import StepRec  # noqa: E402
from chipbench.tracefile import Reduced  # noqa: E402

QWEN = {"n_layers": 28, "d_model": 1024, "n_heads": 16, "n_kv": 8,
        "head_dim": 128, "d_ff": 3072, "vocab": 151936}


def test_rht_qmatmul_counts():
    # 3-bit 1020x6144 (overlapped Hadamard: two 512 blocks), 16 rows
    q = work.QMatrix(d_keep=1020, c=6144, bits=3, d_hat=512,
                     two_blocks=True)
    w = work.rht_qmatmul(16, q)
    assert w.flops == 2 * 16 * 1020 * 6144
    codes = 383 * 6144                      # ceil(1020 * 3 / 8) a column
    assert w.bytes == codes + 4 * 6144 + 4 * 512 * 2 + 4 * 16 * (1020 + 6144)
    # memory bound at 197 TFLOP/s and 819 GB/s
    assert w.bound(197e12, 819e9) == "memory"
    assert w.seconds(197e12, 819e9) == pytest.approx(w.bytes / 819e9)


def test_paged_attention_counts():
    # two slots at 17 and 32 tokens: 2 + 2 live 16-token blocks
    w = work.paged_attention([17, 32], n_heads=16, n_kv=8, head_dim=128,
                             block_size=16, kv_bytes=2)
    assert w.flops == 4 * 16 * 128 * (17 + 32)
    kv = 2 * 16 * 8 * 128 * 2                  # K and V of one block
    assert w.bytes == 4 * kv + 2 * (2 * 4 * 16 * 128)


def test_decode_step_flops():
    per_layer = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                 + 3 * 1024 * 3072)
    dense = 2 * (28 * per_layer + 1024 * 151936)
    assert work.dense_token_flops(QWEN) == dense
    att = 4 * 16 * 128 * 28
    assert work.decode_step_flops(QWEN, [10, 20]) == 2 * dense + att * 30
    assert work.decode_step_flops(QWEN, []) == 0


def committed(name: str) -> dict:
    return json.loads((Path(__file__).resolve().parents[1] / "configs"
                       / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name,flops", [
    # 2 (24 (12,582,912 attention + 50,331,648 FFN) + 189,530,112 lm_head)
    ("internlm2-1.8b", 3_398_959_104),
    # 2 (28 (6,291,456 + 9,437,184) + 155,582,464)
    ("qwen3-0.6b", 1_191_968_768)])
def test_dense_token_flops_of_the_committed_models(name, flops):
    m = committed(name)
    assert work.dense_token_flops(m) == flops
    assert work.dense_token_flops(m, ()) == flops
    att = 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    assert work.decode_step_flops(m, [100, 900]) == 2 * flops + att * 1000


def dense_rht_qmatmul(n, q):
    """The count before grouped matrices: one linear."""
    codes = -(-q.d_keep * q.bits // 8) * q.c
    side = 4 * q.c + 4 * q.d_hat * (2 if q.two_blocks else 1)
    return work.Work(2.0 * n * q.d_keep * q.c,
                     codes + side + 4 * n * (q.d_keep + q.c))


@pytest.mark.parametrize("shape", [(2042, 6144, 3, 1024, True),
                                   (2048, 2048, 4, 2048, False),
                                   (8168, 2048, 2, 4096, True),
                                   (1021, 3072, 5, 512, True)])
@pytest.mark.parametrize("n", [1, 32, 64])
def test_a_linear_counts_as_before(shape, n):
    q = work.QMatrix(*shape)
    assert q.groups == 1 and q.share == 1
    assert work.rht_qmatmul(n, q) == dense_rht_qmatmul(n, q)


def test_grouped_rht_qmatmul_counts():
    # an MoE layer's wi at Mellum2's widths: 8 of the router's 64 experts
    # held, top 8, so one expected (token, held expert) pair a token;
    # d 2304 (Hadamard blocks 2048 and 2048), c = 2 * 896, 3 bits
    q = work.QMatrix(d_keep=2304, c=1792, bits=3, d_hat=2048,
                     two_blocks=True, groups=8, share=8 * 8 / 64)
    w = work.rht_qmatmul(64, q)
    assert w.flops == 2 * 64 * 2304 * 1792
    codes = 864 * 1792                     # 2304 * 3 / 8 bytes a column
    assert w.bytes == (8 * (codes + 4 * 1792) + 4 * 2048 * 2
                       + 4 * 64 * (2304 + 1792))
    # top 2 of 64 with 8 held: a quarter of a row per token
    q = work.QMatrix(d_keep=896, c=2304, bits=4, d_hat=512, two_blocks=True,
                     groups=8, share=2 * 8 / 64)
    w = work.rht_qmatmul(16, q)
    assert w.flops == 2 * 4 * 896 * 2304
    assert w.bytes == (8 * (448 * 2304 + 4 * 2304) + 4 * 512 * 2
                       + 4 * 4 * (896 + 2304))


MELLUM2 = {"n_layers": 28, "d_model": 2304, "n_heads": 32, "n_kv": 4,
           "head_dim": 128, "d_ff": 7168, "vocab": 98304,
           "moe": {"n_experts": 8, "top_k": 8, "d_ff_expert": 896}}


def test_moe_token_flops():
    # 28 (42,467,328 attention linears + 294,912 router + 12,386,304 for
    # the one expected expert of the 8 held) + 452,984,832 lm_head
    held = [work.Experts(router=64, held=8)] * 28
    assert work.dense_token_flops(MELLUM2, held) == 1_997_144_064
    # with two shared experts, each counted whole for every token
    m = dict(MELLUM2, moe=dict(MELLUM2["moe"], n_shared=2))
    assert work.dense_token_flops(m, held) == \
        1_997_144_064 + 28 * 2 * 2 * 3 * 2304 * 896
    # a layer without experts keeps the dense FFN of d_ff
    assert work.dense_token_flops(MELLUM2, held[:27]) == \
        1_997_144_064 - 294_912 - 12_386_304 + 2 * 3 * 2304 * 7168
    att = 4 * 32 * 128 * 28
    assert work.decode_step_flops(MELLUM2, [5, 7], held) == \
        2 * 1_997_144_064 + att * 12


def test_peaks_table_by_device_kind(tmp_path):
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v9 imaginary")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"devices": {}}))
    with pytest.raises(KeyError):
        device.peaks("TPU v5 lite", p)


class Ctx:
    def __init__(self, **kw):
        self.notes = []
        self.__dict__.update(kw)

    def note(self, k, v):
        self.notes.append((k, v))


def trace(kernel_ns=None, module_ns=None, module_calls=None, busy=0.0,
          window=1.0):
    return Reduced(window, busy, 1, kernel_ns or {}, {}, module_ns or {},
                   module_calls or {}, [], [])


PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_roofline_reader_arithmetic():
    q = work.QMatrix(d_keep=256, c=512, bits=4, d_hat=256, two_blocks=False)
    steps = [StepRec(end=1.0, chunk=8, contexts=[5, 6]),
             StepRec(end=2.0, chunk=0, contexts=[7, 8])]
    ideal = (2 * work.rht_qmatmul(4, q).bytes
             + work.rht_qmatmul(8, q).bytes) / 1e9
    ctx = Ctx(trace=trace({"rht_qmatmul": 2 * ideal * 1e9}), steps=steps,
              qmatrices=[q], slots=4, peaks=PEAKS)
    assert reader("rht_qmatmul_roofline.batch")(ctx) == pytest.approx(50.0)
    assert ctx.notes == [("rht_qmatmul bound", "memory")]
    ctx.trace = trace()
    assert reader("rht_qmatmul_roofline.batch")(ctx) is None


def test_roofline_reader_counts_grouped_matrices():
    lin = work.QMatrix(d_keep=256, c=512, bits=4, d_hat=256,
                       two_blocks=False)
    grp = work.QMatrix(d_keep=256, c=256, bits=3, d_hat=256,
                       two_blocks=False, groups=4, share=0.5)
    steps = [StepRec(end=1.0, chunk=8, contexts=[5, 6])]
    total = (work.rht_qmatmul(4, lin) + work.rht_qmatmul(8, lin)
             + work.rht_qmatmul(4, grp) + work.rht_qmatmul(8, grp))
    ideal = total.seconds(PEAKS["bf16_flops"], PEAKS["hbm_bytes_per_s"])
    ctx = Ctx(trace=trace({"rht_qmatmul": 4 * ideal * 1e9}), steps=steps,
              qmatrices=[lin, grp], slots=4, peaks=PEAKS)
    assert reader("rht_qmatmul_roofline.batch")(ctx) == pytest.approx(25.0)


def test_paged_attention_reader_and_mfu():
    m = dict(QWEN, n_layers=2)
    steps = [StepRec(end=1.0, chunk=0, contexts=[16, 40])]
    one = work.paged_attention([16, 40], 16, 8, 128, 16, 2)
    ideal = 2 * one.bytes / 1e9
    ctx = Ctx(trace=trace({"paged_attention": 4 * ideal * 1e9},
                          {"jit__step": 1e9}, {"jit__step": 1}),
              steps=steps, model=m, block_size=16, kv_bytes=2, peaks=PEAKS,
              experts=())
    assert reader("paged_attention_roofline.longchat")(ctx) == \
        pytest.approx(25.0)
    flops = work.decode_step_flops(m, [16, 40])
    assert reader("decode_mfu.longchat")(ctx) == pytest.approx(
        100 * flops / 1e12)
    assert reader("decode_step_ms.longchat")(ctx) == pytest.approx(1000.0)


def test_device_idle_reader():
    ctx = Ctx(trace=trace(busy=0.75e9, window=1e9))
    assert reader("device_idle.batch")(ctx) == pytest.approx(25.0)
    ctx = Ctx(trace=trace(busy=0.0, window=1e9))
    assert reader("device_idle.batch")(ctx) is None
