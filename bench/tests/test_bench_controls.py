"""The comparison that decides ``correct`` fails what it must: the
configuration's control (every capacity cut to a sixteenth, so windows
clip) and the faults a cell can have, each planted under the timed path:
an answer altered where it is published, and half of each chunk's
windows left out."""
import jax.numpy as jnp
import pytest

from ._rehearse import rehearse


CELLS = ["cquery1.tumble.sat", "q15q16.slide75.sat"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(capsys, cell):
    res = rehearse(capsys, cell, "--control", "caps_sixteenth")
    assert res["correct"] is False
    assert res["checks"]["windows_overflowed"]["value"] > 0
    assert res["checks"]["windows_mismatched"]["value"] > 0


def _altered(orig):
    def publish(out_w, cap):
        out = orig(out_w, cap)
        return out._replace(o=out.o.at[0].add(jnp.uint32(1)))
    return publish


def _half_left_out(orig):
    def publish(out_w, cap):
        w = out_w.valid.shape[0]
        keep = (jnp.arange(w) < (w + 1) // 2)[:, None]
        return orig(out_w._replace(valid=out_w.valid & keep), cap)
    return publish


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_publish_faults_fail(capsys, monkeypatch, fault, cell):
    from repro.core import operator

    monkeypatch.setattr(operator, "publish_chunk",
                        fault(operator.publish_chunk))
    res = rehearse(capsys, cell)
    assert res["correct"] is False
    assert res["checks"]["windows_mismatched"]["value"] > 0
