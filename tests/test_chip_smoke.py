"""chip_smoke.py off the chip: it must fail, never carry on.

The driver runs ``python3 chip_smoke.py`` first in a sandbox with no
accelerator, where it has to exit non-zero without the success line.  With
``--rehearse`` (tiny sizes, cpu and Pallas interpreter allowed — rehearsal 1
of the on-chip-measurement guide) the same phases run end to end, and the
success line still never appears.  Each run is a subprocess: the test
process holds jax.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*argv, cwd=ROOT, script=SMOKE, timeout=600, xla_flags=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # the test mesh's 8 virtual devices
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    proc = subprocess.run([sys.executable, script] + list(argv), cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    return proc, lines


def _no_success_line(lines):
    return not any(line.get("ok") for line in lines)


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc, lines = _run()
    assert proc.returncode != 0
    assert "jax found no TPU" in proc.stderr
    assert _no_success_line(lines)
    # it got as far as naming the device it refused, and no further
    assert [line["phase"] for line in lines] == ["device"]
    assert lines[0]["platform"] == "cpu"
    assert lines[0]["accelerator_is_real"] is False


def test_alone_in_a_directory_it_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to drive."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, lines = _run(cwd=str(tmp_path),
                       script=str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert "No module named 'mxnet_tpu'" in proc.stderr
    assert lines == []


@pytest.mark.parametrize("argv", [["--bogus"], ["--chips", "2"],
                                  ["--cpu"]], ids=" ".join)
def test_rehearse_chips_and_seed_are_the_only_options(argv):
    proc, lines = _run(*argv)
    assert proc.returncode == 2 and lines == []
    assert "usage:" in proc.stderr


def test_rehearsal_runs_every_phase_and_never_prints_the_success_line():
    proc, lines = _run("--rehearse", "--seed", "3")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert _no_success_line(lines)
    assert [line.get("phase") for line in lines] == [
        "device", "train", "serve", "serve_hybrid", "retention", "kernels",
        "done", None]
    by = {line["phase"]: line for line in lines[:-1]}
    assert by["train"]["compiles_after_warmup"] == 0
    assert by["train"]["timing_end"]["block_until_ready_waits"] is True
    hybrid = by["serve_hybrid"]
    assert hybrid["counters"]["kernels.paged_attention"] == \
        hybrid["decode_iterations"] > 0
    assert len(hybrid["state_arrays"]) == 6 and hybrid["tokens_flipped"] == 0
    assert hybrid["counters"]["serving.moe_experts_hit"] > 0
    assert hybrid["counters"]["kernels.grouped_matmul"] \
        == hybrid["decode_iterations"] + len(hybrid["prompt_lens"])
    assert set(hybrid["grouped_routes"].values()) == {"grouped"}
    retention = by["retention"]
    assert retention["state_shape"] == [2, 2, 136, 16]
    assert max(retention["scan_err"], retention["update_err"]) \
        <= retention["tolerance"]
    # the update's kernel (interpreted) against its twin at whole lanes;
    # behind the server the toy's heads of 16 take the twin, counted once
    # an R block a decode dispatch
    assert retention["kernel_route"] == "retention"
    assert retention["kernel_err"] <= retention["kernel_tolerance"]
    assert retention["served_routes"] == {"decode-w1": "xla"}
    assert retention["counters"] == {
        "kernels.retention_update": 0,
        "kernels.retention_fallback": retention["decode_iterations"]}
    assert retention["decode_iterations"] > 0
    serve = by["serve"]
    assert serve["counters"]["kernels.paged_attention"] == \
        serve["decode_iterations"] > 0
    assert serve["counters"]["kernels.paged_fallback"] == 0
    assert serve["counters"]["serving.compiles"] == 0
    assert set(serve["paged_routes"].values()) == {"paged"}
    assert serve["worst_logit_gap"] <= serve["logit_gap_tolerance"]
    assert by["kernels"]["interpreted"] is True
    assert {"flash_bwd", "paged_int8", "grouped_bfloat16",
            "pallas_row_softmax"} <= {
        name.split("/")[0] for name in by["kernels"]["kernels"]}
    assert lines[-1]["rehearsed"] == ["phase_train", "phase_serve",
                                      "phase_serve_hybrid",
                                      "phase_retention", "phase_kernels"]
    assert lines[-1]["device"]["platform"] == "cpu"


def test_rehearsal_of_the_four_chip_path_runs_only_that_path():
    """``--chips 4`` on four virtual cpu devices (rehearsal 2): the device
    phase and the cross-chip comparisons, no other phase, count 4."""
    proc, lines = _run("--rehearse", "--chips", "4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert _no_success_line(lines)
    assert [line.get("phase") for line in lines] == [
        "device", "four_chips", "done", None]
    four = lines[1]
    assert four["resnet"]["dp4"]["batch_on"] == [0, 1, 2, 3]
    assert four["resnet"]["one"]["batch_on"] == [0]
    assert all(v == [0, 1, 2, 3] for v in four["lm"]["tp_leaves_on"].values())
    assert lines[-1]["device"]["count"] == 4
