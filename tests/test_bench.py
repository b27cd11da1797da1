"""bench.py: partial-results flush, and one owner per chip.

Every completed config's row must be on disk BEFORE the next one starts,
so a killed sweep (driver timeout) keeps its finished measurements. Covered two ways: in-process (the flush file is readable
and complete after every row) and for real — a subprocess SIGKILLs itself
mid-sweep and the completed rows are found on disk.
"""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def _tiny_cfg():
    from dataclasses import replace

    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet50", "synthetic", **{
        "train.rpn_pre_nms_top_n": 128, "train.rpn_post_nms_top_n": 32,
        "train.batch_rois": 16, "train.max_gt_boxes": 4,
        "train.batch_images": 1, "network.anchor_scales": (2, 4),
        "image.pad_shape": (64, 64)})
    return cfg.with_updates(train=replace(cfg.train, compute_dtype="f32"))


def test_bench_update_config_produces_numbers():
    """The update microbench must yield a real timing."""
    out = bench.bench_update_config(_tiny_cfg(), reps=1, iters=2)
    assert out["tree_ms"] > 0
    assert out["param_leaves"] > 100
    assert out["optimizer"] == "sgd"


import pytest


@pytest.mark.compile_heavy
def test_bench_config_rows_carry_cost_fields():
    """The graftprof acceptance gate (CPU backend path): every bench row
    carries `hbm_bytes` and `pad_waste` computed from the compiled
    executable's cost_analysis()/memory_analysis(), plus the compile-zoo
    accounting (`compile_s`/`n_executables`). `mfu` is a device metric:
    a CPU rehearsal has no published peak to divide by and carries
    None, never a number under that name."""
    from dataclasses import replace

    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet50", "synthetic", **{
        "train.rpn_pre_nms_top_n": 128, "train.rpn_post_nms_top_n": 32,
        "train.batch_rois": 16, "train.max_gt_boxes": 4,
        "train.batch_images": 8,  # the CPU mesh shards over 8 devices
        "network.anchor_scales": (2, 4),
        "image.pad_shape": (64, 64)})
    cfg = cfg.with_updates(
        train=replace(cfg.train, compute_dtype="f32"))
    row = bench.bench_config(cfg, reps=1, iters=2)
    assert row["img_s_per_chip"] > 0
    assert row["mfu"] is None
    # graftcast: every row names its compute dtype (this cfg pins f32),
    # the ledger's cross-dtype comparison guard
    assert row["compute_dtype"] == "f32"
    assert row["hbm_bytes"] > 0
    # make_batch's content size is canvas-proportional (600/640 x
    # 1000/1024), so the padding fraction is a fixed known quantity
    assert row["pad_waste"] == pytest.approx(
        1 - (64 * 600 // 640) * (64 * 1000 // 1024) / (64 * 64), abs=1e-3)
    assert row["compile_s"] >= 0 and row["n_executables"] >= 0


def test_run_sweep_on_row_sees_every_completed_row(tmp_path):
    """The ledger hook: on_row fires per completed config — including
    error rows — in sweep order (bench.main appends each to the perf
    ledger the moment it lands, the partial.json durability contract)."""
    seen = []

    def runner(cfg):
        if cfg == "boom":
            raise RuntimeError("cell failed")
        return {"img_s_per_chip": 3.0}

    bench.run_sweep({"a": "a", "b": "boom"}, runner, attempts=1,
                    on_row=lambda name, row: seen.append((name, row)))
    assert [s[0] for s in seen] == ["a", "b"]
    assert seen[0][1]["img_s_per_chip"] == 3.0
    assert "error" in seen[1][1]


def test_run_sweep_flushes_after_every_config(tmp_path):
    flush = str(tmp_path / "partial.json")
    seen = []

    def runner(cfg):
        if seen:  # previous rows must already be durable
            with open(flush, "r", encoding="utf-8") as fh:
                on_disk = json.load(fh)
            assert all(k in on_disk for k in seen), (seen, on_disk)
        if cfg == "boom":
            raise RuntimeError("cell failed")
        seen.append(cfg)
        return {"img_s_per_chip": 1.0, "which": cfg}

    detail = bench.run_sweep({"a": "a", "b": "boom", "c": "c"}, runner,
                             flush_path=flush, attempts=1)
    with open(flush, "r", encoding="utf-8") as fh:
        on_disk = json.load(fh)
    assert set(on_disk) == {"a", "b", "c"}
    assert on_disk["b"]["error"].startswith("RuntimeError")
    assert detail == on_disk


def test_run_sweep_retries_then_records_error(tmp_path):
    calls = []

    def runner(cfg):
        calls.append(cfg)
        raise ValueError("always down")

    detail = bench.run_sweep({"x": "x"}, runner, attempts=2)
    assert len(calls) == 2  # one retry
    assert "error" in detail["x"]


def test_flush_partial_is_atomic(tmp_path):
    path = str(tmp_path / "p.json")
    bench.flush_partial(path, {"a": 1})
    bench.flush_partial(path, {"a": 1, "b": 2})
    with open(path, "r", encoding="utf-8") as fh:
        assert json.load(fh) == {"a": 1, "b": 2}
    assert not os.path.exists(path + ".tmp")


def test_flush_partial_coerces_non_json_values(tmp_path):
    """A row with a stray np scalar must degrade in place, not raise and
    kill the rest of the sweep."""
    import numpy as np

    path = str(tmp_path / "p.json")
    bench.flush_partial(path, {"a": {"ms": np.float32(1.5),
                                     "n": np.int64(3)}})
    with open(path, "r", encoding="utf-8") as fh:
        row = json.load(fh)["a"]
    assert row["ms"] == 1.5 and row["n"] == 3


def test_partial_rows_survive_sigkill(tmp_path):
    """The acceptance gate: kill the run mid-sweep, find the completed
    rows on disk. SIGKILL (no atexit, no finally) is the honest analog of
    a sweep killed at its time limit."""
    flush = str(tmp_path / "partial.json")
    script = textwrap.dedent(f"""
        import os, signal, sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import bench

        def runner(cfg):
            if cfg == "die":
                os.kill(os.getpid(), signal.SIGKILL)
            return {{"img_s_per_chip": 2.0, "which": cfg}}

        bench.run_sweep({{"first": "first", "die": "die", "never": "never"}},
                        runner, flush_path={flush!r}, attempts=1)
        print("UNREACHABLE")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=110)
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    assert "UNREACHABLE" not in proc.stdout
    with open(flush, "r", encoding="utf-8") as fh:
        on_disk = json.load(fh)
    assert on_disk == {"first": {"img_s_per_chip": 2.0, "which": "first"}}


def _tiny_update_cell():
    return {"tiny_update": (bench.bench_update_config, _tiny_cfg())}


def _bench_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the children inherit it
    monkeypatch.setenv("MX_RCNN_BENCH_OBS", str(tmp_path / "obs"))
    monkeypatch.setenv("MX_RCNN_PERF_LEDGER", str(tmp_path / "hist.jsonl"))
    monkeypatch.delenv("MX_RCNN_BENCH_PARTIAL", raising=False)
    monkeypatch.delenv("MX_RCNN_BENCH_ROUND", raising=False)


def test_main_runs_a_tiny_cell_in_a_child_and_names_the_device(
        monkeypatch, tmp_path, capsys):
    """One owner per chip: bench.main's default path measures every cell
    in a spawn child while the parent never touches jax — rehearsed here
    with one tiny cell on the CPU. The row, the printed line and the
    history row all name the device."""
    _bench_env(monkeypatch, tmp_path)
    rc = bench.main(cells=_tiny_update_cell(), platform="cpu")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["errored"] == []
    row = line["detail"]["tiny_update"]
    assert row["tree_ms"] > 0
    for fields in (row, line["device"]):
        assert fields["platform"] == "cpu" and fields["device_kind"]
        assert fields["device_count"] >= 1
    assert line["value"] is None  # no C4 cell ran: no headline, not 0.0
    with open(tmp_path / "hist.jsonl", encoding="utf-8") as fh:
        hist = [json.loads(l) for l in fh]
    assert [h["config"] for h in hist] == ["tiny_update"]
    assert hist[0]["platform"] == "cpu"
    assert not os.path.exists(os.path.join(REPO, "PERF_LEDGER.jsonl")) \
        or os.path.getmtime(os.path.join(REPO, "PERF_LEDGER.jsonl")) \
        < os.path.getmtime(tmp_path / "hist.jsonl")


def test_main_errors_without_a_tpu(monkeypatch, tmp_path, capsys):
    """No TPU is an error, not a row: told to measure on a TPU with only
    the CPU there, the probe child fails, nothing is measured and the
    exit code is non-zero."""
    _bench_env(monkeypatch, tmp_path)
    rc = bench.main(cells=_tiny_update_cell(), platform="tpu",
                    backend_deadline_s=1.0)
    out = capsys.readouterr()
    assert rc == 1 and out.out.strip() == ""
    assert "no 'tpu' device" in out.err
    assert not os.path.exists(tmp_path / "hist.jsonl")


def test_errored_cell_makes_the_sweep_exit_nonzero(monkeypatch, tmp_path,
                                                   capsys):
    """A sweep in which any cell errored exits non-zero, and the printed
    line says which."""
    _bench_env(monkeypatch, tmp_path)
    rc = bench.main(cells={"broken": (bench.bench_update_config, None)},
                    platform="cpu")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and line["errored"] == ["broken"]
    assert "AttributeError" in line["detail"]["broken"]["error"]


def test_importing_bench_leaves_jax_alone():
    """The parent of a sweep stays off jax: importing bench, building the
    flagship cells and reading the history must not import it."""
    script = ("import sys; sys.path.insert(0, %r); import bench; "
              "bench.flagship_cells(); "
              "assert 'jax' not in sys.modules, 'bench imported jax'" % REPO)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=110)
    assert proc.returncode == 0, proc.stderr[-2000:]
