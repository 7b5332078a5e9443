"""The Phi-3-shaped tiny cell (``tiny_phi3.py``: MHA, head_dim 96, no
query/key norm, eps 1e-5, an untied head of 500 rows) served through the
benchmark at a CPU size: the program passes the comparison and the float8
control does not."""
import types

import jax
import pytest

from chipbench import reference, run
from chipbench.tests import tiny_phi3


@pytest.fixture
def phi3_root(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    return tiny_phi3.make_root(tmp_path)


def test_served_phi3_shape_passes_and_float8_control_fails(phi3_root):
    seed = 2 ** 31 + 77
    # an 8-s window: this width serves about 500 tokens in it on the CPU
    out = run.serve_cell(types.SimpleNamespace(
        workload=tiny_phi3.CELL, seed=seed, seconds=8.0, trace=0), phi3_root)
    assert out.failed == 0 and out.checked
    assert out.c["program_arch"] == "phi3-mini-3.8b"
    res = reference.max_gap(out.c, seed, out.checked, control=True)
    assert res["tokens"] >= 200
    assert run.judge(out.limits, res, out.failed)[0] is True
    correct, checks = run.judge(out.limits, reference.as_control(res),
                                out.failed)
    assert correct is False
    assert not checks["mean_logit_gap"]["ok"]
    assert not checks["widest_logit_gap"]["ok"]
