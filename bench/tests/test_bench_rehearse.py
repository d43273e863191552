"""Every cell runs end to end on the CPU at its tiny rehearsal size,
agrees with the plain reference window by window, and prints no device
number."""
import pytest

from bench import spec as S

from ._rehearse import rehearse

CELLS = [w["name"] for w in S.benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_correct(capsys, workload):
    res = rehearse(capsys, workload, seed=2**31 + 3)
    assert res["rehearsal"] is True
    assert "metrics" not in res and "device" not in res
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
