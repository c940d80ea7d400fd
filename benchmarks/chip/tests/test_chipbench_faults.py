"""Each fault a serving cell can have, planted in the timed path of a run
past the look for a chip (CPU, ``tiny.json``, the cell's committed
limits), comes out not correct: a token altered where it is sampled, and a
step that returns its KV state unchanged.  In the batch mix the planted step
is the decode step; in the chat mix it is the prefill chunk, which leaves
the session histories and the new turn unwritten.  The faults are those
``control.py --fault`` plants on the chip (``faults.py``).
"""
import pytest

from test_chipbench_run import limits, tiny_run, values

import faults  # noqa: E402  (beside the harness, on the path from here)


@pytest.mark.parametrize("mix", ["batch", "longchat"])
def test_altered_token_is_not_correct(mix):
    with faults.altered_token():
        res = tiny_run(mix)
    assert not res["correct"], res["check"]
    assert values(res)["gap_mean"] > limits(mix)["gap_mean"]


@pytest.mark.parametrize("mix,step", [("batch", "decode_step_paged"),
                                      ("longchat", "prefill_chunk_paged")])
def test_unchanged_kv_state_is_not_correct(mix, step):
    with faults.FAULTS[step]():
        res = tiny_run(mix)
    assert not res["correct"], res["check"]
    assert values(res)["gap_mean"] > limits(mix)["gap_mean"]
