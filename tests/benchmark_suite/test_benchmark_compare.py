"""The comparison's own arithmetic (``benchmark/compare.py``) and the
path by which recorded readings are judged again under the limits as
committed (``benchmark/calibrate.py --judge``). No device, no model."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import calibrate, compare  # noqa: E402

LEAVES = ["a", "b", "c", "d", "e", "f", "g", "h"]


def tree(values):
    return dict(zip(LEAVES, values))


def snapshot(m1, delta, moved, loss=(5.0,)):
    return {"loss": list(loss), "first": {"m1": tree(m1)},
            "last": {"delta": tree(delta), "moved": tree(moved)}}


REF_M1 = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]
REFERENCE = dict(snapshot(REF_M1, REF_M1, [100] * 8),
                 grad1=tree([10.0] * 7 + [1e-6]))


@pytest.mark.parametrize("factor", [0.5, 1.0, 1.3])
def test_all_gap_is_the_gap_of_the_norms_taken_together(factor):
    program = tree([factor * v for v in REF_M1])
    assert compare.all_gap(program, tree(REF_M1)) \
        == pytest.approx(abs(factor - 1.0))
    gaps = compare.leaf_gaps(program, tree(REF_M1))
    # a leaf under the median leaf is measured against the median's norm
    assert gaps["a"] == pytest.approx(abs(factor - 1.0) * 1.0 / 4.5)
    assert gaps["h"] == pytest.approx(abs(factor - 1.0))


def test_readings_leave_still_leaves_out_of_the_change():
    program = snapshot(REF_M1[:7] + [80.0], REF_M1[:7] + [80.0],
                       [100] * 7 + [3])
    values, notes = compare.readings(program, REFERENCE)
    assert notes["still_leaves"] == ["h"]
    assert values["delta_worst"] == pytest.approx(0.0)
    assert values["moved_worst"] == pytest.approx(0.0)
    assert values["m1_worst"] == pytest.approx(72.0 / 8.0)
    assert values["loss_gap"] == pytest.approx(0.0)


@pytest.mark.parametrize("value,limit,ok", [
    (0.1, 0.3, True), (0.3, 0.3, True), (0.31, 0.3, False),
    (float("nan"), 0.3, False)])
def test_decide_holds_each_number_to_its_own_limit(value, limit, ok):
    correct, compared = compare.decide(
        {"m1_all": value, "loss_gap": 0.0},
        {"m1_all": limit, "loss_gap": 0.001})
    assert correct is ok
    assert set(compared) == {"m1_all", "loss_gap"}
    assert calibrate.verdict({"m1_all": value, "loss_gap": 0.0},
                             {"m1_all": limit, "loss_gap": 0.001}) \
        == {"correct": ok, "over": [] if ok else ["m1_all"]}


def test_judge_reads_recorded_readings_under_the_limits(tmp_path,
                                                        capsys):
    path = tmp_path / "cal.jsonl"
    rows = [{"seed": 1, "what": "program", "values": {"m1_all": 0.05}},
            {"seed": 1, "what": "control_int8",
             "values": {"m1_all": 1.2}},
            {"seed": 2, "what": "program", "values": {"m1_all": 0.08}}]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    seen = calibrate.judge(str(path), {"m1_all": 0.3})
    assert seen == {"program": [True, True], "control_int8": [False]}
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[1]["over"] == ["m1_all"]

