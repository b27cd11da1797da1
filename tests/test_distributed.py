"""Multi-process (multi-host analog) DP: 2 processes × 4 CPU devices.

The reference's `dist_sync` KVStore has no testable analog in its repo
(SURVEY.md §5: multi-GPU is "tested" only by running it); here the
jax.distributed path (parallel/distributed.py) is exercised for real: two
spawned processes form one 8-device mesh, each feeds its local half of a
fixed global batch, and both must agree bit-for-bit on the loss and the
updated parameter checksum (the gradient all-reduce spans the process
boundary).
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.compile_heavy

WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
# Force CPU with 4 virtual devices BEFORE jax import.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

from mx_rcnn_tpu.parallel.distributed import maybe_initialize_distributed
maybe_initialize_distributed()

import jax, numpy as np
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.train.optimizer import build_optimizer
from mx_rcnn_tpu.train.step import create_train_state, make_train_step

cfg = generate_config("resnet50", "synthetic", **{
    "image.pad_shape": (64, 64),
    "network.anchor_scales": (2, 4),
    "train.rpn_pre_nms_top_n": 128, "train.rpn_post_nms_top_n": 32,
    "train.batch_rois": 16, "train.max_gt_boxes": 4,
    "train.batch_images": 1,
})
model = zoo.build_model(cfg)
params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
tx = build_optimizer(cfg, params, steps_per_epoch=10)
state = create_train_state(params, tx)
mesh = create_mesh("8")
step = make_train_step(model, cfg, mesh=mesh, donate=False)

# Global batch of 8 images, deterministic; this process slices its half.
rank = jax.process_index()
rs = np.random.RandomState(0)
g_img = rs.randn(8, 64, 64, 3).astype(np.float32)
gt = np.zeros((8, 4, 4), np.float32); gt[:, 0] = [8, 8, 40, 40]
valid = np.zeros((8, 4), bool); valid[:, 0] = True
cls = np.zeros((8, 4), np.int32); cls[:, 0] = 1
local = slice(rank * 4, rank * 4 + 4)
batch = {
    "image": g_img[local],
    "im_info": np.asarray([[64, 64, 1.0]] * 4, np.float32),
    "gt_boxes": gt[local], "gt_classes": cls[local],
    "gt_valid": valid[local],
}
state, metrics = step(state, shard_batch(batch, mesh), jax.random.PRNGKey(7))
loss = float(metrics["TotalLoss"])
ck = float(sum(jax.numpy.sum(jax.numpy.abs(l)).astype(jax.numpy.float64)
               for l in jax.tree.leaves(state.params)))
print(f"RESULT rank={rank} loss={loss:.8f} checksum={ck:.6f}", flush=True)
"""


def _run_two_process_workers(worker_src: str, tmp_path) -> dict:
    """Spawn 2 coordinated worker processes (MXRCNN_* env contract), wait,
    and return {rank: {key: value-string}} parsed from each RESULT line."""
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(worker_src)
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "MXRCNN_COORDINATOR": f"127.0.0.1:{port}",
            "MXRCNN_NUM_PROCESSES": "2",
            "MXRCNN_PROCESS_ID": str(rank),
        })
        env.pop("JAX_PLATFORMS", None)  # worker sets its own
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed worker timed out")
        outs.append(out)
    results = {}
    for out, p in zip(outs, procs):
        assert p.returncode == 0, out[-3000:]
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT")][0]
        kv = dict(part.split("=") for part in line.split()[1:])
        results[int(kv.pop("rank"))] = kv
    assert set(results) == {0, 1}
    return results


@pytest.mark.slow
def test_two_process_dp(tmp_path):
    results = _run_two_process_workers(WORKER, tmp_path)
    # Replicated state: both processes computed the SAME loss and params.
    assert results[0] == results[1], results


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


TP_WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

from mx_rcnn_tpu.parallel.distributed import maybe_initialize_distributed
maybe_initialize_distributed()

import jax, numpy as np
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.parallel.partition import shard_train_state, tp_param_specs
from mx_rcnn_tpu.train.optimizer import build_optimizer
from mx_rcnn_tpu.train.step import create_train_state, make_train_step

cfg = generate_config("detr_r50", "synthetic", **{
    "image.pad_shape": (64, 64),
    "network.detr_queries": 10,
    "network.detr_hidden": 32,
    "network.detr_heads": 2,
    "network.detr_enc_layers": 1,
    "network.detr_dec_layers": 1,
    "network.norm": "group",
    "network.freeze_at": 0,
    "train.compute_dtype": "f32",
    "network.tensor_parallel": True,
    "train.max_gt_boxes": 4,
    "train.batch_images": 1,
})
model = zoo.build_model(cfg)
params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
tx = build_optimizer(cfg, params, steps_per_epoch=10)
state = create_train_state(params, tx)
# (data=4, model=2): the DP gradient psum crosses the process boundary,
# the Megatron TP collectives stay intra-process (the ICI-like axis).
mesh = create_mesh("4x2")
specs = tp_param_specs(state.params)
state = shard_train_state(state, mesh, specs)
step = make_train_step(model, cfg, mesh=mesh, donate=False,
                       forward_fn=zoo.forward_train, param_specs=specs)

rank = jax.process_index()
rs = np.random.RandomState(0)
g_img = rs.randn(4, 64, 64, 3).astype(np.float32)
gt = np.zeros((4, 4, 4), np.float32); gt[:, 0] = [8, 8, 40, 40]
valid = np.zeros((4, 4), bool); valid[:, 0] = True
cls = np.zeros((4, 4), np.int32); cls[:, 0] = 1
local = slice(rank * 2, rank * 2 + 2)
batch = {
    "image": g_img[local],
    "im_info": np.asarray([[64, 64, 1.0]] * 2, np.float32),
    "gt_boxes": gt[local], "gt_classes": cls[local],
    "gt_valid": valid[local],
}
state, metrics = step(state, shard_batch(batch, mesh), jax.random.PRNGKey(7))
loss = float(metrics["TotalLoss"])
ck = float(sum(jax.numpy.sum(jax.numpy.abs(l)).astype(jax.numpy.float64)
               for l in jax.tree.leaves(state.params)))
n_sharded = sum(1 for l in jax.tree.leaves(state.params)
                if not l.sharding.is_fully_replicated)
print(f"RESULT rank={rank} loss={loss:.8f} checksum={ck:.6f} "
      f"sharded={n_sharded}", flush=True)
"""


@pytest.mark.slow
def test_two_process_dp_tp(tmp_path):
    """DP x TP across a process boundary: 2 processes x 4 devices form a
    (4, 2) mesh; Megatron-sharded DETR weights, gradient psum spanning
    the processes. Both ranks must agree bit-for-bit."""
    results = _run_two_process_workers(TP_WORKER, tmp_path)
    assert results[0] == results[1], results
    assert int(results[0]["sharded"]) > 0, "no TP-sharded leaves"


PP_WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

from mx_rcnn_tpu.parallel.distributed import maybe_initialize_distributed
maybe_initialize_distributed()

import jax, numpy as np
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.train.optimizer import build_optimizer
from mx_rcnn_tpu.train.step import create_train_state, make_train_step

cfg = generate_config("vitdet_b", "synthetic", **{
    "image.pad_shape": (64, 64),
    "network.vit_dim": 32,
    "network.vit_depth": 2,
    "network.vit_heads": 2,
    "network.vit_window": 4,
    "train.compute_dtype": "f32",
    "network.pp_stages": 2,
    "network.vit_drop_path": 0.0,  # the staged backbone runs none
    "network.anchor_scales": (2, 4),
    "train.fpn_rpn_pre_nms_per_level": 64,
    "train.rpn_post_nms_top_n": 32,
    "train.batch_rois": 16,
    "train.max_gt_boxes": 4,
    "train.batch_images": 2,
})
# INTERLEAVE the global device list so the (4, 2) mesh's model axis
# pairs one device from EACH process: the GPipe ppermute ring hops
# across the process boundary (cross-"host" pipeline), while the data
# axis stays local per process.
devs = jax.devices()
order = [devs[i + 4 * p] for i in range(4) for p in range(2)]
mesh = create_mesh("4x2", order)
# The point of this worker: every model-axis pair must span BOTH
# processes, or the ppermute ring never crosses a process boundary and
# the test passes vacuously.
for row in mesh.devices:
    assert {d.process_index for d in row} == {0, 1}, mesh.devices
model = zoo.build_model(cfg, mesh=mesh)
params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
tx = build_optimizer(cfg, params, steps_per_epoch=10)
state = create_train_state(params, tx)
step = make_train_step(model, cfg, mesh=mesh, donate=False,
                       forward_fn=zoo.forward_train)

# With the interleaved order every data row spans BOTH processes (each
# holds one model-half of every row), so process-local data for the
# P("data") sharding is the FULL global batch — each process feeds all
# 8 images and make_array_from_process_local_data takes the rows its
# devices cover.
# 8 global images: 2 per data shard, and each 4-image microbatch still
# divides over the 4-way data axis (pipeline_apply guard).
rank = jax.process_index()
rs = np.random.RandomState(0)
g_img = rs.randn(8, 64, 64, 3).astype(np.float32)
gt = np.zeros((8, 4, 4), np.float32); gt[:, 0] = [8, 8, 40, 40]
valid = np.zeros((8, 4), bool); valid[:, 0] = True
cls = np.zeros((8, 4), np.int32); cls[:, 0] = 1
batch = {
    "image": g_img,
    "im_info": np.asarray([[64, 64, 1.0]] * 8, np.float32),
    "gt_boxes": gt, "gt_classes": cls,
    "gt_valid": valid,
}
state, metrics = step(state, shard_batch(batch, mesh), jax.random.PRNGKey(7))
loss = float(metrics["TotalLoss"])
ck = float(sum(jax.numpy.sum(jax.numpy.abs(l)).astype(jax.numpy.float64)
               for l in jax.tree.leaves(state.params)))
print(f"RESULT rank={rank} loss={loss:.8f} checksum={ck:.6f}", flush=True)
"""


@pytest.mark.slow
def test_two_process_dp_pp(tmp_path):
    """DP x PP with the pipeline ring CROSSING the process boundary: the
    mesh model axis pairs one device from each process (interleaved
    order), so every GPipe ppermute hop is a cross-process transfer.
    Ranks must agree bit-for-bit."""
    results = _run_two_process_workers(PP_WORKER, tmp_path)
    assert results[0] == results[1], results
