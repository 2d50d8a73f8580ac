"""The program's vision path: ``launch/steps.build_vision_train``."""
from __future__ import annotations

import dataclasses


def plan(sizes: dict, mix: dict, mesh):
    """The program's training step for ``sizes`` under ``mix`` on ``mesh``."""
    import jax.numpy as jnp

    from repro.configs.registry import ShapeCell, get_arch
    from repro.launch.steps import build_vision_train, make_exchange
    from repro.models.resnet import ResNetConfig
    from repro.optim.optimizers import OptimizerSpec

    if sizes["stem_width"] != 64 or sizes["gn_eps"] != 1e-5:
        raise ValueError("the program's ResNet has a stem of 64 channels and "
                         "GroupNorm eps 1e-5")
    cfg = ResNetConfig(name=sizes["name"], blocks=tuple(sizes["blocks"]),
                       widths=tuple(sizes["widths"]),
                       n_classes=sizes["n_classes"], groups=sizes["groups"],
                       dtype=jnp.dtype(sizes["dtype"]))
    arch = dataclasses.replace(get_arch(sizes.get("arch", "resnet50")),
                               config=cfg,
                               microbatches={"bench": mix["microbatches"]})
    cell = ShapeCell("bench", "train", {"global_batch": mix["global_batch"],
                                        "img": sizes["image_size"]})
    ex = make_exchange(mesh, "vision", mix["strategy"],
                       opt=OptimizerSpec(**sizes["optimizer"]))
    return build_vision_train(arch, cell, mesh, ex)
