"""Rehearsal of `chip_smoke.py` in tier-1: every phase function at the
tiny preset on the 8-device CPU mesh (kernels interpreted), so the control
flow the chip run depends on cannot rot between chip runs — and the
contract's refusal to run anywhere but on a TPU."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, ROOT)
    try:
        return importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(ROOT)


_REHEARSAL = """
import sys
import chip_smoke
names = [n for n, _ in chip_smoke.PHASES]
verdicts = chip_smoke.run_phases(
    chip_smoke.PRESETS["tiny"], names, require_four_chips=True
)
sys.exit(0 if list(verdicts.values()) == ["PASS"] * len(names) else 1)
"""


@pytest.fixture(scope="module")
def rehearsal():
    """Every phase at the tiny preset, in ONE fresh process on the suite's
    8-device CPU mesh (inherited through XLA_FLAGS): the phases bring their
    own process group up and down, which must not touch the session's."""
    import subprocess

    return subprocess.run(
        [sys.executable, "-c", _REHEARSAL], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


@pytest.mark.parametrize(
    "name",
    ["collectives", "mnist_ddp", "lm_train", "kernel_numerics", "serve",
     "four_chip"],
)
def test_phase_passes_at_tiny_preset(rehearsal, name):
    out = rehearsal.stdout
    assert f"{name}: PASS" in out, out + rehearsal.stderr[-2000:]


def test_rehearsal_reports_what_it_ran_on(rehearsal):
    out = rehearsal.stdout
    assert rehearsal.returncode == 0, out + rehearsal.stderr[-2000:]
    assert "collectives: PASS" in out and "world=8" in out
    # off-TPU the kernel is interpreted, and the phase says so rather
    # than claiming the Mosaic path
    assert "Pallas interpreter" in out and "Mosaic kernel" not in out
    assert "fully_shard fsdp x tp = (2, 2)" in out
    assert "replica placement" in out
    assert "FAIL" not in out and "SKIP" not in out


def test_phase_table_matches_presets(smoke):
    assert [n for n, _ in smoke.PHASES] == [
        "collectives", "mnist_ddp", "lm_train", "kernel_numerics", "serve",
        "four_chip",
    ]
    # full width is the point of the chip preset: only depth may be cut
    lm = smoke.PRESETS["chip"]["lm"]
    assert (lm["d_model"], lm["n_heads"], lm["d_ff"], lm["vocab"],
            lm["seq"]) == (2048, 16, 5504, 32000, 2048)
    assert lm["depths"][0] == 16 and list(lm["depths"]) == sorted(
        lm["depths"], reverse=True
    )


def test_main_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    # a string exit status is printed to stderr and exits 1
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""  # no result of any kind


def test_failed_phase_fails_the_run(smoke, monkeypatch, capsys):
    def boom(preset):
        raise RuntimeError("injected")

    monkeypatch.setattr(
        smoke, "PHASES", (("collectives", boom), ("serve", lambda p: "ok"))
    )
    verdicts = smoke.run_phases({}, ["collectives", "serve"])
    out = capsys.readouterr().out
    # later phases still run and report
    assert verdicts == {"collectives": "FAIL", "serve": "PASS"}
    assert "collectives: FAIL" in out and "injected" in out
    assert "serve: PASS" in out


def test_skip_is_never_a_pass(smoke, monkeypatch, capsys):
    def skip(preset):
        raise smoke.Skip("devices=1")

    monkeypatch.setattr(smoke, "PHASES", (("four_chip", skip),))
    assert smoke.run_phases({}, ["four_chip"]) == {
        "four_chip": "SKIP(devices=1)"
    }
    assert "four_chip: SKIP(devices=1)" in capsys.readouterr().out
    required = smoke.run_phases({}, ["four_chip"], require_four_chips=True)
    assert required["four_chip"].startswith("FAIL (required")
    assert "FAIL (required" in capsys.readouterr().out


def test_result_line_shape(smoke, monkeypatch, capsys):
    """With a TPU reported and every phase passing (four_chip may SKIP on
    one chip), the LAST stdout line is the contract's JSON object and
    nothing else; the line before it says what each phase did."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    seen = []

    def skip(preset):
        raise smoke.Skip("devices=1")

    monkeypatch.setattr(smoke, "_require_tpu", lambda: device)
    monkeypatch.setattr(
        smoke, "PHASES",
        (("collectives", lambda p: seen.append(p) or "ok"),
         ("four_chip", skip)),
    )
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2] == "phases: collectives PASS; four_chip SKIP(devices=1)"
    # main() runs the full-width preset and has no way to be told otherwise
    assert seen == [smoke.PRESETS["chip"]]

    monkeypatch.setattr(
        smoke, "PHASES", (("collectives", lambda p: 1 / 0),)
    )
    assert smoke.main([]) == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert not last.startswith("{")


def test_subset_run_prints_no_result_line(smoke, monkeypatch, capsys):
    """The result line means 'every phase'; a `--only` run cannot end in
    the same line as a full one."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    monkeypatch.setattr(smoke, "_require_tpu", lambda: device)
    monkeypatch.setattr(
        smoke, "PHASES",
        (("collectives", lambda p: "ok"), ("serve", lambda p: "ok")),
    )
    assert smoke.main(["--only", "collectives"]) == 0
    out = capsys.readouterr().out
    assert "serve did not run" in out
    assert not any(l.startswith("{") for l in out.splitlines())


def test_no_flag_selects_a_smaller_width(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main(["--preset", "tiny"])
    assert exc.value.code == 2  # argparse: unrecognized arguments
    assert "--preset" in capsys.readouterr().err
