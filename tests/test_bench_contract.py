"""The bench driver's output contract: the LAST stdout line is one parseable
JSON record with metric/value/unit/vs_baseline, a leg that finds no
accelerator exits non-zero with no record, and a CPU number never appears
under a device metric's name (bench.py's contract)."""
import io
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


def _capture_main(monkeypatch, records, force_cpu=False, argv=()):
    """Run bench.main() with _run_subprocess_record stubbed; return (rc,
    parsed stdout lines, the legs the parent asked for)."""
    calls = []

    def fake_run(argv, budget):
        calls.append(argv)
        return records.get(argv[0])

    monkeypatch.setattr(bench, "_run_subprocess_record", fake_run)
    monkeypatch.setenv("SHEEPRL_TPU_PROGRESS", "0")  # main() setdefaults it
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    if force_cpu:
        monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(sys, "argv", ["bench.py", *argv])
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    try:
        rc = bench.main()
    finally:
        sys.stdout = sys.__stdout__
    lines = [json.loads(ln) for ln in out.getvalue().strip().splitlines() if ln.strip()]
    return rc, lines, calls


REQUIRED = {"metric", "value", "unit", "vs_baseline"}


def test_headline_is_e2e_with_step_extra(monkeypatch):
    step = {"metric": "step", "value": 1000.0, "unit": "steps/s", "vs_baseline": 500.0}
    e2e = {"metric": "e2e", "value": 100.0, "unit": "env steps/sec", "vs_baseline": 10.0}
    rc, lines, calls = _capture_main(monkeypatch, {"dv3_step": step, "dv3": e2e})
    rec = lines[-1]
    assert rc == 0 and REQUIRED <= rec.keys()
    assert rec["metric"] == "e2e"
    assert rec["extra_metrics"][0]["metric"] == "step"
    # one subprocess per leg, and nothing else: the parent probes no device
    assert [c[0] for c in calls] == ["dv3_step", "dv3"]


def test_step_record_promoted_when_e2e_fails(monkeypatch):
    step = {"metric": "step", "value": 1000.0, "unit": "steps/s", "vs_baseline": 500.0}
    rc, lines, _ = _capture_main(monkeypatch, {"dv3_step": step})
    rec = lines[-1]
    assert rc == 0 and REQUIRED <= rec.keys()
    assert rec["metric"] == "step"
    assert "e2e_error" in rec


def test_no_chip_default_path_fails_with_error_record_and_no_cpu_value(monkeypatch):
    """Every leg failed (what a machine without a chip gives: each leg exits
    non-zero, see the next test): the last line is still one parseable
    record, it carries an error and no measurement, nothing is rerun on the
    CPU, and the exit code is non-zero."""
    rc, lines, calls = _capture_main(monkeypatch, {})
    rec = lines[-1]
    assert rc != 0
    assert REQUIRED <= rec.keys()
    assert rec["value"] == 0.0 and rec["vs_baseline"] == 0.0 and "error" in rec
    assert "platform" not in rec  # no cpu-fallback label: there is no fallback
    assert [c[0] for c in calls] == ["dv3_step", "dv3"]  # no probe, no retry, no second try on the CPU
    assert "BENCH_FORCE_CPU" not in os.environ  # the parent never switches the legs to the CPU


@pytest.mark.parametrize("leg", ["dv3_step", "dv3", "ppo", "anakin", "dv3_fleet"])
def test_leg_without_accelerator_exits_nonzero_and_prints_no_record(monkeypatch, leg):
    """This process has the CPU backend only (tests/conftest.py): a leg must
    refuse before it measures anything, so no CPU number can land under the
    name of a device metric."""
    ran = []
    for name in ("bench_recipe", "bench_dreamer_e2e", "bench_dreamer_fleet", "bench_anakin"):
        monkeypatch.setattr(bench, name, lambda *a, _n=name, **k: ran.append(_n) or {})
    import bench_dv3

    monkeypatch.setattr(bench_dv3, "record", lambda: ran.append("record") or {})
    with pytest.raises(SystemExit) as exc:
        _capture_main(monkeypatch, {}, argv=[leg])
    assert exc.value.code not in (0, None)
    assert ran == []


def test_forced_cpu_leg_is_labelled_in_platform_metric_and_baseline(monkeypatch):
    """The operator's explicit BENCH_FORCE_CPU=1 stays: the leg runs on the
    host, and its record says so in `platform`, in the metric's own name, and
    by carrying no comparison with the accelerator baseline and no MFU."""
    measured = {"metric": "DreamerV3-S gradient steps/sec/chip", "value": 3.0, "unit": "steps/s",
                "vs_baseline": 1.5, "mfu": 0.2, "peak_flops_assumed": 1e12}
    import bench_dv3

    monkeypatch.setattr(bench_dv3, "record", lambda: dict(measured))
    rc, lines, calls = _capture_main(monkeypatch, {}, force_cpu=True, argv=["dv3_step"])
    rec = lines[-1]
    assert rc == 0 and calls == []
    assert rec["platform"] == "cpu-forced"
    assert rec["metric"].startswith("[cpu-forced") and "BENCH_FORCE_CPU" in rec["metric"]
    assert rec["vs_baseline"] is None and "mfu" not in rec and "peak_flops_assumed" not in rec
    assert rec["value"] == 3.0  # the host number itself is kept, under its label


def test_forced_cpu_default_path_runs_both_legs_without_probe(monkeypatch):
    e2e = {"metric": "[cpu-forced ...] e2e", "value": 3.0, "unit": "env steps/sec",
           "vs_baseline": None, "platform": "cpu-forced"}
    rc, lines, calls = _capture_main(monkeypatch, {"dv3": e2e}, force_cpu=True)
    assert rc == 0 and lines[-1]["platform"] == "cpu-forced"
    assert [c[0] for c in calls] == ["dv3_step", "dv3"]
