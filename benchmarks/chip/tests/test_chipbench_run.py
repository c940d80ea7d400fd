"""The rest of a run, past the look for a chip, on the CPU at the size in
``tiny.json``: a sound run is correct; the control (the program's quantizer
at half the stated bit budget) and each fault a serving cell can have,
planted in the timed path, come out not correct (``test_chipbench_faults``).

Every run but the control's is held to the committed limits of the cell
whose mix it serves (``limits/<cell>.json``): only the model is cut, to
widths a test run can hold.  The readings the limits were set from are in
``PERF.md``.
"""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import cell  # noqa: E402

TINY = json.loads((Path(__file__).parent / "tiny.json").read_text())
CELLS = {"batch": "internlm2-1.8b.batch", "longchat": "qwen3-0.6b.longchat"}
SEED = 2 ** 33 + 101


CHAT = {"name": CELLS["longchat"], "config": "qwen3-0.6b",
        "traffic": "longchat", "chips": 1}


def load_bench() -> dict:
    """``BENCHMARK.json``, with the chat cell whose files are kept here for
    the benchmark change that measures it (``PERF.md``)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if all(w["name"] != CHAT["name"] for w in bench["workloads"]):
        bench["workloads"].append(CHAT)
    return bench


def limits(mix: str) -> dict:
    return cell.load_json(HERE / "limits" / f"{CELLS[mix]}.json")


def tiny_run(mix: str, seed: int = SEED, bits=None, lims=None) -> dict:
    """One run at the tiny size, with the prefix cache on or off as the
    cell's own configuration has it."""
    bench = load_bench()
    c = next(w for w in bench["workloads"] if w["name"] == CELLS[mix])
    conf = json.loads(json.dumps(TINY["conf"]))
    conf["pool"]["prefix_cache"] = cell.load_json(
        HERE / "configs" / f"{c['config']}.json")["pool"]["prefix_cache"]
    return cell.run_cell(bench, CELLS[mix], seed, 1.0, False, conf=conf,
                         mix=TINY["mixes"][mix], limits=lims,
                         peaks=TINY["peaks"], bits=bits)


def values(res):
    return {k: v["value"] for k, v in res["check"].items()}


@pytest.mark.parametrize("mix", ["batch", "longchat"])
def test_sound_run_is_correct(mix):
    res = tiny_run(mix)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == set(cell.metric_names(
        load_bench(), CELLS[mix], False))


@pytest.mark.parametrize("mix", ["batch", "longchat"])
def test_control_is_not_correct(mix):
    """The control fails the tiny model's own limit (``tiny.json``), set
    between its sound runs and its control as the cells' limits are set
    between theirs: at this size the control reads too little to fail the
    full-width limits on every run."""
    bits = TINY["conf"]["quant"]["avg_bits"] / 2
    lim = {**limits(mix), **TINY["control_limits"]}
    res = tiny_run(mix, bits=bits, lims=lim)
    assert not res["correct"], res["check"]


