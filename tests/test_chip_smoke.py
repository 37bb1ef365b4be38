"""``chip_smoke.py`` — the stages the driver runs on the chip, imported and
driven here at tiny sizes on the CPU harness (8 forced host devices, so the
several-devices stage runs too). The test passes the platform it expects;
the command line always demands a TPU and must never report a CPU run."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

import chip_smoke


def test_stages_pass_at_tiny_sizes(capsys):
    device = chip_smoke.run(
        "cpu", rows=30_000, logit_rows=20_000, logit_cols=16,
        ingest_chunk_bytes=32_768, shard_min_rows=1024, matmul_n=256)
    assert device["platform"] == "cpu" and device["count"] >= 2
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    # every report line names the device; every stage reported
    assert all(ln["device"] == device for ln in lines)
    stages = {ln["stage"][0] for ln in lines}
    assert stages >= set("ABCEF")
    assert lines[-1]["stage"] == "done" and lines[-1]["ok"] is True
    b = next(ln for ln in lines if "second_pass_compiles" in ln)
    assert b["chunks"] >= 4
    assert not any(b["second_pass_compiles"].values())


def test_a_wrong_platform_is_a_failure_not_a_report():
    with pytest.raises(chip_smoke.SmokeFailure, match="expected platform"):
        chip_smoke.run("tpu")


def test_command_line_demands_a_tpu_and_prints_no_result(capsys):
    assert chip_smoke.main() == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_peak_comes_from_the_benchmarks_table_and_an_unknown_kind_raises():
    with open(os.path.join(chip_smoke.REPO, "benchmarks", "peaks.json")) as f:
        table = json.load(f)
    assert (chip_smoke.peak_bf16_flops_per_s("TPU v5 lite")
            == table["TPU v5 lite"]["bf16_flops_per_s"])
    with pytest.raises(KeyError, match="no published peaks"):
        chip_smoke.peak_bf16_flops_per_s("TPU v0 none")
