"""``chip_smoke.py`` on the CPU: its device gate refuses the CPU, and its
phases run end to end at smoke widths (the chip runs them at full
width), so an API change cannot break the script unnoticed."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.configs import SMOKES  # noqa: E402


def test_gate_refuses_cpu_and_prints_no_verdict(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_serve_phase_at_smoke_width():
    chip_smoke.check_serve(SMOKES["qwen2-0.5b"], batch=2, prompt_len=16,
                           n_new=4, max_seq=64)


def test_fleet_phase():
    chip_smoke.check_fleet()


def test_session_phase_at_smoke_width():
    chip_smoke.check_session(SMOKES["qwen2-0.5b"])
