"""The program's dense LM path: ``launch/steps.build_lm_train``."""
from __future__ import annotations

import dataclasses


def plan(sizes: dict, mix: dict, mesh):
    """The program's training step for ``sizes`` under ``mix`` on ``mesh``."""
    import jax.numpy as jnp

    from repro.configs.registry import ShapeCell, get_arch
    from repro.launch.steps import build_lm_train, make_exchange
    from repro.models.transformer import TransformerConfig
    from repro.optim.optimizers import OptimizerSpec

    cfg = TransformerConfig(
        name=sizes["name"], n_layers=sizes["n_layers"],
        d_model=sizes["d_model"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
        d_ff=sizes["d_ff"], vocab=sizes["vocab"],
        rope_theta=sizes["rope_theta"], eps=sizes["rms_eps"],
        dtype=jnp.dtype(sizes["dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
        remat=sizes["remat"], attn_chunk=sizes["attn_chunk"])
    arch = dataclasses.replace(get_arch(sizes.get("arch", "internlm2-1.8b")),
                               config=cfg,
                               microbatches={"bench": mix["microbatches"]})
    cell = ShapeCell("bench", "train", {"global_batch": mix["global_batch"],
                                        "seq_len": mix["seq_len"]})
    ex = make_exchange(mesh, "lm", mix["strategy"],
                       opt=OptimizerSpec(**sizes["optimizer"]))
    return build_lm_train(arch, cell, mesh, ex)
