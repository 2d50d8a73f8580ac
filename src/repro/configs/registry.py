"""Architecture registry: the 10 assigned archs, Moonlight-16B-A3B (the
DeepSeek-V3 block) and the paper's ResNet-50.

Each arch file exposes ``ARCH: ArchDef``; the registry imports them all and
serves (arch × shape) cells to the launcher, dry-run and benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str  # train | prefill | decode | decode_long | serve | retrieval |
    # graph_full | graph_minibatch | graph_full_large | graph_molecule
    params: dict
    skip_reason: str | None = None


@dataclasses.dataclass(frozen=True)
class ArchDef:
    arch_id: str
    family: str  # lm | gnn | recsys | vision
    config: Any
    smoke_config: Any
    cells: tuple
    microbatches: dict | None = None  # per-shape grad-accum override
    notes: str = ""

    def cell(self, name: str) -> ShapeCell:
        for c in self.cells:
            if c.name == name:
                return c
        raise KeyError(f"{self.arch_id} has no shape {name}")


def _build() -> dict:
    from repro.configs import (
        autoint,
        dien,
        dlrm_mlperf,
        equiformer_v2,
        gemma3_1b,
        granite_moe_1b,
        internlm2_1_8b,
        moonlight_16b_a3b,
        qwen2_72b,
        qwen2_moe_a2_7b,
        resnet50,
        xdeepfm,
    )

    mods = [
        gemma3_1b, internlm2_1_8b, qwen2_72b, granite_moe_1b, qwen2_moe_a2_7b,
        equiformer_v2, dlrm_mlperf, autoint, dien, xdeepfm, resnet50,
        moonlight_16b_a3b,
    ]
    return {m.ARCH.arch_id: m.ARCH for m in mods}


ARCHS: dict | None = None


def get_arch(arch_id: str) -> ArchDef:
    global ARCHS
    if ARCHS is None:
        ARCHS = _build()
    return ARCHS[arch_id]


def list_archs() -> list:
    global ARCHS
    if ARCHS is None:
        ARCHS = _build()
    return list(ARCHS)


def list_cells(assigned_only: bool = True) -> list:
    """All (arch, shape) cells of the assigned matrix (excludes resnet50)."""
    out = []
    for a in list_archs():
        if assigned_only and a == "resnet50":
            continue
        arch = get_arch(a)
        for c in arch.cells:
            out.append((a, c.name))
    return out
