"""chip_smoke.py off the chip: it must FAIL here, and fail honestly.

The smoke passes only on a TPU (the driver runs it there). What tier-1 can
hold is the other half of its contract: with the CPU it exits non-zero with
``"ok": false``; alone in a directory it exits non-zero and prints no
result; a phase that raises ends the run — nothing is caught and carried
past; and the phases themselves (entry points, log and event parsing, the
rerun-from-cache comparison, the eval) work end to end at a tiny size, which
is the guide's first rehearsal kept as a test.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_SETS = (
    "image.scales=((96,160),)", "image.pad_shape=(160,160)",
    "train.rpn_pre_nms_top_n=256", "train.rpn_post_nms_top_n=64",
    "test.rpn_pre_nms_top_n=128", "test.rpn_post_nms_top_n=32",
    "train.batch_rois=32", "train.max_gt_boxes=8",
    "network.anchor_scales=(2,4,8)")


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def test_smoke_does_not_pass_on_a_cpu(tmp_path):
    """The command as the driver runs it, where there is no chip."""
    work = tmp_path / "repo"
    work.mkdir()
    # a copy, so the run's report and work dir stay out of the checkout
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), work)
    os.symlink(os.path.join(REPO, "mx_rcnn_tpu"), work / "mx_rcnn_tpu")
    proc = _run(work, "chip_smoke.py")
    assert proc.returncode != 0, proc.stdout[-2000:]
    last = _last_json(proc.stdout)
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": last["device"]["kind"],
        "count": last["device"]["count"]}}
    assert "no 'tpu' here" in proc.stderr
    assert not (work / ".chip_smoke").exists()  # no phase ran


def test_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(tmp_path, "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def tiny_smoke(monkeypatch, tmp_path):
    """The smoke's phases steered to a tiny size on the CPU — in the test,
    not by an option of the script."""
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "NETWORK", "resnet50")
    monkeypatch.setattr(chip_smoke, "N_TRAIN", 3)
    monkeypatch.setattr(chip_smoke, "N_VAL", 2)
    monkeypatch.setattr(chip_smoke, "BASE_SETS",
                        chip_smoke.BASE_SETS + TINY_SETS)
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "REPORT", str(tmp_path / "report.json"))
    monkeypatch.setattr(chip_smoke, "REPORT_FIELDS", {})
    return tmp_path


def test_a_phase_that_raises_ends_the_run(tiny_smoke, monkeypatch, capsys):
    """No phase's exception is caught and carried past: the train phase is
    made to raise, and the run ends there — exit by exception (non-zero),
    ``"ok": false`` as the last line and in the written report."""
    def broken(*args, **kwargs):
        raise RuntimeError("train phase broke")

    monkeypatch.setattr(chip_smoke, "train_phase", broken)
    with pytest.raises(RuntimeError, match="train phase broke"):
        chip_smoke.main([])
    out = capsys.readouterr().out
    assert _last_json(out)["ok"] is False
    assert "smoke: dataset" in out and "smoke: eval" not in out
    with open(tiny_smoke / "report.json", encoding="utf-8") as fh:
        assert json.load(fh)["ok"] is False


@pytest.mark.compile_heavy
def test_phases_run_end_to_end_at_a_tiny_size(tiny_smoke, capsys):
    """Rehearsal 1 of the on-chip-measurement guide, kept: the same code
    the chip runs — train_end2end.py twice, test.py on its checkpoint —
    at a tiny size on the CPU. It reports ok (for the patched platform)
    with reproducible finite losses, zero train-step compiles after the
    first step and a second run served from the compile cache."""
    assert chip_smoke.main([]) == 0
    out = capsys.readouterr().out
    assert _last_json(out)["ok"] is True
    with open(tiny_smoke / "report.json", encoding="utf-8") as fh:
        report = json.load(fh)
    a, b = report["train[a]"], report["train[b]"]
    assert a["steps"] == 6 and a["losses"] == b["losses"]  # 3 images + flips
    assert a["train_step_compiles_after_first_step"] == 0
    assert report["rerun"]["cache_hits"] >= 1
    assert a["checkpoints"] and report["eval"]["images"] == 2
    assert "AP" in report["eval"]["results"]
    # on the CPU the jnp NMS stands in — which is why this is no chip run
    assert report["tpu_custom_calls_in_lowered_programs"] == {
        "train_step": 0, "detect": 0}


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside_or_inside_the_checkout(
        tmp_path, placed):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache is there and the
    code sets no other directory; where it is not, it is <repo>/.jax_cache
    — never the home directory, a temporary name, a pid or a time."""
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache\n"
        "import jax\n"
        "print(enable_persistent_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n" % REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path / "home"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if placed:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=110)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, want]
    assert not (tmp_path / "home").exists()
