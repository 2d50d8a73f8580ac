"""resnet50 — the paper's own ImageNet workload (not part of the assigned
40-cell matrix; used by the paper-faithful benchmarks)."""
from repro.configs.registry import ArchDef, ShapeCell
from repro.models.resnet import ResNetConfig

CONFIG = ResNetConfig()

SMOKE = ResNetConfig(
    name="resnet-smoke", blocks=(1, 1, 1, 1), widths=(32, 64, 128, 256),
    n_classes=10, groups=8,
)

ARCH = ArchDef(
    arch_id="resnet50",
    family="vision",
    config=CONFIG,
    smoke_config=SMOKE,
    cells=(
        ShapeCell("imagenet_train", "train",
                  {"global_batch": 256, "img": 224}),
    ),
    # 2 x 128 per chip: batch 256 at 224^2 in f32 is over a 16 GB v5e's HBM
    microbatches={"imagenet_train": 2},
    notes="pure data-parallel over the data axis; the paper's Figure 3 "
    "workload class",
)
