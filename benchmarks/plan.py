"""``--plan 1``: compile the cell's step for a DESCRIBED v5e (no chip, no run)
and print what the compiler says it needs - the tool that sizes a cell's
batch against the memory floor before the first chip-minute. Run it with
``JAX_PLATFORMS=cpu``."""

from __future__ import annotations

import json
import os


def run(cell: dict, conf: dict, mix: dict):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from benchmarks.drivers.train import _program_config
    from mx_rcnn_tpu.models.zoo import build_model
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    if mix["driver"] != "train":
        raise SystemExit(f"--plan knows no driver kind {mix['driver']!r}")
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = cell["chips"]
    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model"))
    cfg = _program_config(conf)
    model = build_model(cfg, mesh=mesh)
    step = make_train_step(model, cfg, mesh=mesh, donate=True)
    # nms_dispatch asks the backend which NMS to use; the plan is for a TPU
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = step.lower(*abstract_step_inputs(
            model, cfg, mesh, cfg.train.batch_images * chips)).compile()
    finally:
        jax.default_backend = real
    m = compiled.memory_analysis()
    out = {"workload": cell["name"], "chips": chips,
           "batch_per_chip": cfg.train.batch_images,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "alias_bytes": m.alias_size_in_bytes,
           "temp_bytes": m.temp_size_in_bytes,
           "generated_code_bytes": m.generated_code_size_in_bytes}
    out["total_gb"] = (out["argument_bytes"] + out["output_bytes"]
                       - out["alias_bytes"] + out["temp_bytes"]) / 1e9
    print(json.dumps(out))
    return out
