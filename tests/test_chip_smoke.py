"""``chip_smoke.py`` rehearsed on the CPU: its phases at reduced size
with the Pallas kernels in interpret mode, and its refusal to report a
result when JAX finds no TPU."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


@pytest.mark.parametrize("phase", ["a", "b"])
def test_smoke_serve_phase_rehearsal(smoke, phase):
    arch, kv, wd, required = smoke.SERVE_PHASES[phase]
    rec = smoke.serve_phase(arch, kv, wd, required, reduced=True,
                            kernel_mode="interpret", n_requests=5,
                            prompt_range=(20, 90), max_new=6, max_len=128)
    assert rec["kernel_mode"] == "interpret"
    assert rec["fallbacks"] == {}
    assert all(rec["kernels"][k] > 0 for k in required)
    assert rec["generated_tokens"] > 0
    assert rec["mixed_iters"] > 0 and rec["decode_steps"] > 0
    assert 0.0 <= rec["greedy_match_share"] <= 1.0
    assert rec["warm_outputs_identical"]
    json.dumps(rec)


def test_smoke_oracle_phase_rehearsal(smoke):
    rec = smoke.oracle_phase(interpret=True)
    assert rec["oracle_max_error"] <= smoke.ORACLE_TOL
    # every kernel of the path, at both widths
    assert len(rec["oracle_errors"]) == 2 * 8
