"""Each fault a serving cell can have, planted in the timed path of a run
past the look for a chip (CPU, ``tiny.json``, the cell's committed
limits), comes out not correct: a token altered where it is sampled, and a
step that returns its KV state unchanged.  In the batch mix the planted
step is the decode step; in the chat mix it is the prefill chunk, which
leaves the session histories and the new turn unwritten.
"""
import pytest

from test_chipbench_run import limits, tiny_run, values


@pytest.mark.parametrize("mix", ["batch", "longchat"])
def test_altered_token_is_not_correct(monkeypatch, mix):
    from repro.serve.engine import PagedServer
    sample = PagedServer._sample

    def altered(self, logits, rid, step):
        tok = sample(self, logits, rid, step)
        return (tok + 1) % logits.shape[-1] if step % 4 == 2 else tok

    monkeypatch.setattr(PagedServer, "_sample", altered)
    res = tiny_run(mix)
    assert not res["correct"], res["check"]
    assert values(res)["gap_mean"] > limits(mix)["gap_mean"]


@pytest.mark.parametrize("mix,step", [("batch", "decode_step_paged"),
                                      ("longchat", "prefill_chunk_paged")])
def test_unchanged_kv_state_is_not_correct(monkeypatch, mix, step):
    from repro.models import decode
    real = getattr(decode, step)

    def stale(cfg, params, caches, *a, **k):
        logits, _ = real(cfg, params, caches, *a, **k)
        return logits, caches

    monkeypatch.setattr(decode, step, stale)
    res = tiny_run(mix)
    assert not res["correct"], res["check"]
