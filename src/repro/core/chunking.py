"""Parameter-space chunking: the PHub "fine grained key chunking" layer.

The paper splits the model's parameter space into fixed-size chunks (32 KB)
*independent of tensor boundaries* and assigns chunks to processing cores in a
balanced, locality-preserving way.  Here the same idea maps a pytree of
parameters into a single padded 1-D array partitioned into chunks, with a
balanced chunk -> device assignment over the PS mesh axes.

Key properties (tested in tests/test_chunking.py):
  * round-trip: unflatten(flatten(tree)) == tree exactly, any dtypes/shapes
  * chunk size is a multiple of the TPU tile (8*128 lanes) so each chunk maps
    onto whole VMEM tiles in the fused aggregation kernel
  * balance: with D owners and C chunks, every owner holds floor(C/D) or
    ceil(C/D) chunks -- independent of per-tensor sizes
  * determinism: layout depends only on (tree structure, shapes, dtypes)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# One TPU f32 tile is (8, 128); chunks are multiples of this so BlockSpecs in
# kernels/fused_agg_opt tile exactly.  Default chunk = 32 KB of f32 = 8192
# elements, mirroring the paper's 32 KB key chunks.
TILE_ELEMS = 8 * 128
DEFAULT_CHUNK_ELEMS = 8192


@dataclasses.dataclass(frozen=True)
class TensorSlot:
    """Placement of one leaf tensor inside the flat parameter space."""

    name: str
    shape: tuple[int, ...]
    dtype: Any
    offset: int  # element offset in the flat space
    size: int  # number of elements


@dataclasses.dataclass(frozen=True)
class ParamSpace:
    """Static layout of a pytree in a chunked flat address space.

    The flat space is padded to ``num_chunks * chunk_elems`` where
    ``num_chunks`` is also padded up to a multiple of ``num_owners`` so that
    the chunk space reshapes exactly to ``(num_owners, chunks_per_owner,
    chunk_elems)`` -- each owner (PS micro-shard) gets an identical-size slab,
    which is what makes reduce-scatter/all-gather exchange and per-owner
    fused updates shape-uniform.
    """

    slots: tuple[TensorSlot, ...]
    treedef: Any
    chunk_elems: int
    num_owners: int
    payload_elems: int  # sum of leaf sizes (no padding)
    flat_elems: int  # padded total

    # ---- derived ----
    @property
    def num_chunks(self) -> int:
        return self.flat_elems // self.chunk_elems

    @property
    def chunks_per_owner(self) -> int:
        return self.num_chunks // self.num_owners

    @property
    def elems_per_owner(self) -> int:
        return self.flat_elems // self.num_owners

    @property
    def padding_elems(self) -> int:
        return self.flat_elems - self.payload_elems

    # ---- construction ----
    @staticmethod
    def build(
        tree: Any,
        *,
        chunk_elems: int = DEFAULT_CHUNK_ELEMS,
        num_owners: int = 1,
    ) -> "ParamSpace":
        if chunk_elems % TILE_ELEMS != 0:
            raise ValueError(
                f"chunk_elems must be a multiple of {TILE_ELEMS}, got {chunk_elems}"
            )
        if num_owners < 1:
            raise ValueError("num_owners must be >= 1")

        leaves, treedef = jax.tree.flatten(tree)
        paths = jax.tree.leaves_with_path(tree)
        slots = []
        offset = 0
        for (path, leaf) in paths:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            slots.append(
                TensorSlot(
                    name=jax.tree_util.keystr(path),
                    shape=tuple(leaf.shape),
                    dtype=jnp.dtype(leaf.dtype),
                    offset=offset,
                    size=size,
                )
            )
            offset += size
        payload = offset
        # pad to a whole number of chunks, then to a multiple of num_owners
        num_chunks = -(-max(payload, 1) // chunk_elems)
        num_chunks = -(-num_chunks // num_owners) * num_owners
        flat = num_chunks * chunk_elems
        return ParamSpace(
            slots=tuple(slots),
            treedef=treedef,
            chunk_elems=chunk_elems,
            num_owners=num_owners,
            payload_elems=payload,
            flat_elems=flat,
        )

    # ---- flatten / unflatten ----
    def flatten(self, tree: Any, dtype=jnp.float32) -> jax.Array:
        """Pack a pytree into the padded flat space (single fused buffer).

        All leaves are cast to ``dtype`` (the PS wire/accumulation dtype; the
        paper's PS aggregates in f32).  Original dtypes are restored on
        unflatten.
        """
        leaves = jax.tree.leaves(tree)
        if len(leaves) != len(self.slots):
            raise ValueError("tree does not match ParamSpace layout")
        parts = [jnp.ravel(leaf).astype(dtype) for leaf in leaves]
        pad = self.flat_elems - self.payload_elems
        if pad:
            parts.append(jnp.zeros((pad,), dtype))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def unflatten(self, flat: jax.Array) -> Any:
        if flat.shape != (self.flat_elems,):
            raise ValueError(
                f"flat has shape {flat.shape}, expected {(self.flat_elems,)}"
            )
        leaves = []
        for slot in self.slots:
            seg = jax.lax.dynamic_slice_in_dim(flat, slot.offset, slot.size)
            leaves.append(seg.reshape(slot.shape).astype(slot.dtype))
        return jax.tree.unflatten(self.treedef, leaves)

    # ---- owner views ----
    def to_owner_slabs(self, flat: jax.Array) -> jax.Array:
        """(flat,) -> (num_owners, elems_per_owner).

        Owner o holds chunks [o*cpo, (o+1)*cpo): a *contiguous* slab.  The
        paper assigns chunks round-robin over cores for NIC locality; on a
        TPU mesh, contiguous slabs give identical balance (every slab is the
        same size by construction) while keeping reduce-scatter a single
        contiguous collective.  See ``owner_of_chunk`` for the map.
        """
        return flat.reshape(self.num_owners, self.elems_per_owner)

    def from_owner_slabs(self, slabs: jax.Array) -> jax.Array:
        return slabs.reshape(self.flat_elems)

    def owner_of_chunk(self, chunk_idx: int) -> int:
        return chunk_idx // self.chunks_per_owner

    def owner_of_offset(self, offset: int) -> int:
        return self.owner_of_chunk(offset // self.chunk_elems)

    # ---- introspection ----
    def describe(self) -> str:
        lines = [
            f"ParamSpace: {len(self.slots)} tensors, payload={self.payload_elems} "
            f"elems, flat={self.flat_elems} elems, chunks={self.num_chunks}x"
            f"{self.chunk_elems}, owners={self.num_owners} "
            f"({self.chunks_per_owner} chunks each), padding="
            f"{self.padding_elems} ({100.0 * self.padding_elems / self.flat_elems:.2f}%)"
        ]
        return "\n".join(lines)


def zeros_like_space(space: ParamSpace, dtype=jnp.float32) -> jax.Array:
    return jnp.zeros((space.flat_elems,), dtype)


def tensor_chunk_map(space: ParamSpace) -> list[tuple[str, int, int]]:
    """For observability: (tensor name, first chunk, last chunk) per tensor."""
    out = []
    for slot in space.slots:
        first = slot.offset // space.chunk_elems
        last = (slot.offset + max(slot.size, 1) - 1) // space.chunk_elems
        out.append((slot.name, first, last))
    return out
