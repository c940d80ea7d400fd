"""A configuration file builds the program's ``ModelConfig`` field by field
(``session.model_config``), and a mixture-of-experts model goes through
the harness's set-up path: quantized, its expert stacks counted as grouped
matrices, served by the engine for a few steps."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import session, work  # noqa: E402

TINY = json.loads((Path(__file__).parent / "tiny.json").read_text())


def committed(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def dense_construction(conf: dict):
    """How the harness built a configuration's model before it read every
    field: a dense model from nine keys."""
    from repro.models.config import ModelConfig
    m = conf["model"]
    return ModelConfig(name=conf["name"], family="dense",
                       n_layers=m["n_layers"], d_model=m["d_model"],
                       n_heads=m["n_heads"], n_kv=m["n_kv"], d_ff=m["d_ff"],
                       vocab=m["vocab"], head_dim=m["head_dim"],
                       rope_theta=m["rope_theta"],
                       qk_norm=bool(m.get("qk_norm", False)))


@pytest.mark.parametrize("conf", [committed("internlm2-1.8b"),
                                  committed("qwen3-0.6b"), TINY["conf"]],
                         ids=["internlm2-1.8b", "qwen3-0.6b", "tiny"])
def test_committed_files_build_the_dense_model_as_before(conf):
    cfg = session.model_config(conf)
    assert cfg == dense_construction(conf)
    assert hash(cfg) == hash(dense_construction(conf))


def tiny_moe(**moe) -> dict:
    """The tiny model as the program's ``moe`` family: 4 experts, top 2,
    with a capacity factor under which no token is dropped."""
    conf = json.loads(json.dumps(TINY["conf"]))
    conf["name"] = "tiny-moe"
    conf["model"].update(family="moe", moe={
        "n_experts": 4, "top_k": 2, "d_ff_expert": 128,
        "capacity_factor": 8.0, **moe})
    return conf


def test_nested_objects_and_lists_become_their_types():
    from repro.models.config import MoEConfig
    conf = tiny_moe()
    conf["model"]["mixer_pattern"] = ["attn"] * conf["model"]["n_layers"]
    cfg = session.model_config(conf)
    assert cfg.family == "moe" and cfg.ffn_kind() == "moe"
    assert cfg.moe == MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                                capacity_factor=8.0)
    assert cfg.mixer_pattern == ("attn",) * 3
    hash(cfg)                       # a static argument of the programs


@pytest.mark.parametrize("where,bad", [
    ("model", {"n_layer": 3}),
    ("moe", {"n_expert": 4}),
    ("model", {"window": {"size": 8}}),
])
def test_an_unknown_key_fails_with_its_name_and_file(where, bad):
    conf = tiny_moe()
    target = conf["model"] if where == "model" else conf["model"]["moe"]
    target.update(bad)
    with pytest.raises(ValueError, match=f"tiny-moe.json.*{next(iter(bad))}"):
        session.model_config(conf, "configs/tiny-moe.json")


def test_moe_model_goes_through_set_up_and_serves():
    import jax
    import numpy as np
    from repro.models import transformer as tf
    from repro.serve import Request
    conf = tiny_moe()
    cfg = session.model_config(conf)
    weights = tf.init_params(cfg, jax.random.PRNGKey(3))
    qparams, rep, cal_s, q_s = session.quantize(conf, cfg, weights, 3)
    assert q_s >= cal_s > 0.0
    qm = session.quantized_matrices(qparams, cfg)
    grouped = [q for q in qm if q.groups > 1]
    # each MoE layer's wi (d, 2 f_e) and wo (f_e, d), every expert held
    assert len(grouped) == 2 * cfg.n_layers
    assert all(q.groups == cfg.moe.n_experts for q in grouped)
    assert all(q.share == cfg.moe.top_k for q in grouped)  # router == held
    assert sorted({(q.d_keep, q.c) for q in grouped}) == [(128, 256),
                                                          (256, 256)]
    assert len(qm) - len(grouped) == 4 * cfg.n_layers   # wq wk wv wo
    assert session.experts(qparams) == [work.Experts(router=4, held=4)] * 3

    engine = session.build_engine(conf, cfg, qparams)
    rng = np.random.default_rng(3)
    for rid in range(3):
        engine.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab, 12 + 5 * rid).astype(np.int32), max_new=4))
    done, steps = [], 0
    while engine.poll() and steps < 60:
        done += engine.step()
        steps += 1
    assert sorted(done) == [0, 1, 2]
    assert engine.stats["decode_steps"] > 0
