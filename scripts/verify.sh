#!/usr/bin/env bash
# Tier-1 verify: the full pytest suite on CPU.  Pallas kernels run in
# interpret mode on any backend that is not a TPU, so this needs no
# accelerator; chip_smoke.py is the check on the chip.
# Usage: scripts/verify.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="${PWD}/src${PYTHONPATH:+:$PYTHONPATH}"
# keep CPU runs deterministic and quiet
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

python -m pytest -x -q "$@"

# smoke the topology + multi-tenant + replication + serve-load +
# sparse-serve benchmarks: their derived-column invariants (core-link
# bytes shrink 1/workers-per-rack, int8 a further ~4x, codec-"none"
# bit-identity; tenant isolation + priority fairness; failover
# bit-identity + exact chain-replication byte accounting;
# version-stamped read bit-identity + staleness bound +
# serve-never-perturbs-training; hot-row exact invalidation + sparse
# sharding independence + exact row wire accounting; default-vs-solved
# plan bit-identity + closed-loop autoscale bit-identity; fused wire-path
# bit-parity vs the unfused three-program pipeline; switch-tier
# exhaustion/failure fallback bit-identity + exact pool byte accounting)
# are asserted inside and fail the run if violated
python -m benchmarks.run \
    --only topo,multijob,replication,serve_load,serve_slo,sparse_serve,placement,kernel,switch_agg >/dev/null

# no in-repo production code on the deprecated PBoxFabric kwarg path
# (src/, benchmarks/, examples/; tests exempt — stdlib-only AST scan)
python scripts/check_deprecated.py

# docs are part of tier-1: intra-repo links/anchors in README + docs/
# must resolve (stdlib-only checker, no network)
python scripts/check_docs.py

# serve smoke: batched generation through a live-fabric read plane (the
# driver bit-verifies every read against the fabric before generating)
python -m repro.launch.serve --arch gemma3-1b --mesh 1x1 --batch 2 \
    --prompt-len 8 --tokens 3 --source fabric --train-rounds 1 >/dev/null

