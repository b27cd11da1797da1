"""The guide's first two rehearsals kept as tests: the benchmark's command,
steered to a tiny size on the CPU, runs each cell's control flow end to end
(four virtual devices for the mesh cell) and prints its last line with no
device metric in it; with the timed path broken underneath, ``correct`` comes
out false; and where there is no accelerator the command gives no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, run  # noqa: E402
from bm_tiny import tiny  # noqa: E402

pytestmark = pytest.mark.compile_heavy
BM = manifest.load()
DEVICE_METRICS = {m["name"] for m in BM["per_layer"]
                  if m["source"] == "device_trace"} | {"step.mfu.train"}


def _run(cell, trace, seed=5, batch_images=2, seconds="0.5"):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     seconds, "--trace", str(trace)], platform="cpu",
                    **tiny(batch_images))


def test_traced_rehearsal_is_correct_and_names_no_device_metric(capsys):
    out = _run("c4_r101_train", trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == out["run"]["images"] > 0
    assert "loop.dispatch_ms.train" in out["metrics"]
    assert not DEVICE_METRICS & set(out["metrics"])
    assert "busy_s" not in out["device"] and out["device"]["platform"] == "cpu"
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert list(last)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())


def test_mesh_cell_rehearsal_on_four_virtual_devices():
    out = _run("c4_r101_train_dp4", trace=0, seed=2 ** 31 + 9, batch_images=1)
    assert out["correct"] is True and out["device"]["count"] == 4
    assert set(out["metrics"]) == {"setup_s", "train_img_per_s_chip"}
    assert out["run"]["images"] == 4 * out["run"]["steps"]
    assert out["metrics"]["train_img_per_s_chip"]["value"] > 0


def _broken(monkeypatch, wrap):
    import mx_rcnn_tpu.tools.train as program

    real = program.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch, key: wrap(step, state, batch, key)

    monkeypatch.setattr(program, "make_train_step", make)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    _broken(monkeypatch, lambda step, state, batch, key:
            (state, step(state, batch, key)[1]))
    out = _run("c4_r101_train", trace=0)
    assert out["correct"] is False
    assert out["compared"]["dw3"]["value"] == pytest.approx(1.0)
    assert set(out["metrics"]) == {"setup_s", "train_img_per_s_chip"}


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    def half(step, state, batch, key):
        h = batch["image"].shape[0] // 2
        return step(state, {k: jnp.concatenate([v[:h], v[:h]])
                            for k, v in batch.items()}, key)

    _broken(monkeypatch, half)
    out = _run("c4_r101_train", trace=0)
    assert out["correct"] is False
    failed = [k for k, v in out["compared"].items() if v["value"] > v["limit"]]
    assert "grad1_rpn" in failed or "dw3_rpn" in failed, out["compared"]


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    """Every chip applying its own rows' gradient is, on the replicated
    state, the first chip's rows alone standing for the whole batch."""
    import jax
    import jax.numpy as jnp

    def one_shard(step, state, batch, key):
        per_chip = batch["image"].shape[0] // 4
        return step(state, {
            k: jax.device_put(jnp.concatenate([v[:per_chip]] * 4), v.sharding)
            for k, v in batch.items()}, key)

    _broken(monkeypatch, one_shard)
    out = _run("c4_r101_train_dp4", trace=0, seed=2 ** 31 + 9, batch_images=1)
    assert out["correct"] is False
    failed = [k for k, v in out["compared"].items() if v["value"] > v["limit"]]
    assert "grad1_rpn" in failed or "dw3_rpn" in failed, out["compared"]


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "c4_r101_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_without_an_accelerator_there_is_no_result():
    proc = _command(REPO)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "no result" in proc.stderr


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BM["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
