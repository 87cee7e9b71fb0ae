"""chip_smoke.py off the chip: its CPU rehearsal (tiny widths, Pallas
interpreted, virtual devices) runs every phase's control flow, and without the
rehearsal option a machine without a chip gets a non-zero exit and no result.
The real thing needs the chip: `python chip_smoke.py` through the chip tool."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd, script=SMOKE, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}  # conftest's 8 virtual devices
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def _assert_rehearsal(proc, lines, phases, count):
    assert proc.returncode == 0, proc.stderr[-3000:]
    by_phase = {rec["phase"]: rec for rec in lines if "phase" in rec}
    for name in phases:
        assert by_phase[name]["ok"] is True and by_phase[name]["rehearsal"] is True, by_phase[name]
    last = lines[-1]
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": count}, "rehearsal": True}
    assert '"tpu' not in proc.stdout  # a rehearsal never reads as a chip run
    return by_phase


def test_rehearsal_runs_every_one_chip_phase_on_the_cpu(tmp_path):
    proc, lines = _run(["--rehearse-cpu"], tmp_path)
    by_phase = _assert_rehearsal(proc, lines, ["dv3", "eval", "ppo", "kernels"], 1)
    dv3 = by_phase["dv3"]
    assert dv3["grad_steps"] >= 8 and dv3["retraces"] == 0 and dv3["train_compiles"] == 1
    assert dv3["learner_devices"] == ["cpu:0"] and dv3["conv_impl"]["resolved"] == "einsum"
    assert by_phase["ppo"]["update_compiles"] == 1 and by_phase["ppo"]["updates"] >= 4
    assert by_phase["kernels"]["pallas_mode"] == "interpret"
    assert not list(tmp_path.iterdir())  # logs, memmaps and checkpoints stayed in the script's own scratch


def test_rehearsal_of_the_four_chip_option_runs_only_the_mesh_phases(tmp_path):
    proc, lines = _run(["--rehearse-cpu", "--chips", "4"], tmp_path)
    by_phase = _assert_rehearsal(proc, lines, ["dv3_dp4", "dv3_dp2_fsdp2"], 4)
    assert set(by_phase) == {"setup", "dv3_dp4", "dv3_dp2_fsdp2"}
    for rec in (by_phase["dv3_dp4"], by_phase["dv3_dp2_fsdp2"]):
        assert len(rec["learner_devices"]) == 4 and rec["placement"]["devices"] == 4
        assert rec["train_compiles"] == 1 and rec["max_rel_diff"] < 5e-3  # the gated world-model losses


def test_without_a_chip_and_without_the_rehearsal_option_it_fails_at_once(tmp_path):
    proc, lines = _run([], tmp_path, timeout=120)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""  # no result of any kind
    assert "no accelerator" in proc.stderr


@pytest.mark.parametrize("args", [[], ["--rehearse-cpu"]], ids=["chip", "rehearsal"])
def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path, args):
    """The script proves THIS repo's program; without the program beside it
    there is nothing to prove."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, _ = _run(args, tmp_path, script=tmp_path / "chip_smoke.py", timeout=120)
    assert proc.returncode not in (0, None)
    assert '"ok"' not in proc.stdout
