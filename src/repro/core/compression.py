"""Gradient compression codecs for the cross-pod (wire) exchange stage.

The paper's in-network aggregation proposal (§3) is constrained to integer
arithmetic with per-packet metadata.  We model that constraint as a chunked
int8 codec: one f32 scale per PS chunk + int8 payload, with error feedback
(residual accumulation) so compression error does not bias convergence.
A cheaper bf16 codec halves wire bytes with no state.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.quant.ops import dequantize_chunks, quantize_chunks


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Codec policy for one logical link: what bits cross the wire.

    ``codec`` picks the representation ("none" | "bf16" | "int8"),
    ``chunk_elems`` the int8 scale granularity (one f32 scale per chunk),
    ``error_feedback`` whether the sender carries the quantization residual
    into its next push, and ``use_pallas`` whether encode/decode run the
    Pallas codec kernels or their jnp oracles (bit-identical either way).
    """

    codec: str = "none"  # "none" | "bf16" | "int8"
    chunk_elems: int = 8192
    error_feedback: bool = True
    use_pallas: bool = True

    @property
    def wire_bytes_per_elem(self) -> float:
        """Average wire bytes per f32 element under this codec.

        A modeling convenience for link-time estimates; exact integer
        accounting (scale bytes charged per started chunk) lives in
        ``wire_bytes``."""
        if self.codec == "none":
            return 4.0
        if self.codec == "bf16":
            return 2.0
        if self.codec == "int8":
            # int8 payload + one f32 scale per chunk
            return 1.0 + 4.0 / self.chunk_elems
        raise ValueError(self.codec)


def wire_bytes(cfg: CompressionConfig, n_elems: int) -> int:
    """Exact wire bytes for an ``n_elems`` slab under ``cfg``.

    Unlike ``wire_bytes_per_elem`` (a per-element average), this is the
    integer byte count the fabric's ServerStats accumulate; for int8 the
    per-chunk f32 scale is charged per started chunk, so chunk-aligned
    slabs account exactly."""
    if cfg.codec == "none":
        return 4 * n_elems
    if cfg.codec == "bf16":
        return 2 * n_elems
    if cfg.codec == "int8":
        return n_elems + 4 * -(-n_elems // cfg.chunk_elems)
    raise ValueError(cfg.codec)


def encode(cfg: CompressionConfig, slab: jax.Array, ef: jax.Array | None):
    """slab (N,) f32 -> (payload tuple, new error-feedback state)."""
    if cfg.codec == "none":
        return (slab,), ef
    if cfg.codec == "bf16":
        # bf16 truncation error is small; EF optional
        if cfg.error_feedback and ef is not None:
            slab = slab + ef
        wire = slab.astype(jnp.bfloat16)
        new_ef = (slab - wire.astype(jnp.float32)) if (cfg.error_feedback and ef is not None) else ef
        return (wire,), new_ef
    if cfg.codec == "int8":
        if cfg.error_feedback and ef is not None:
            slab = slab + ef
        q, scale = quantize_chunks(
            slab, cfg.chunk_elems, use_pallas=cfg.use_pallas
        )
        if cfg.error_feedback and ef is not None:
            deq = dequantize_chunks(
                q, scale, cfg.chunk_elems, use_pallas=cfg.use_pallas
            )
            new_ef = slab - deq
        else:
            new_ef = ef
        return (q, scale), new_ef
    raise ValueError(cfg.codec)


@dataclasses.dataclass(frozen=True)
class WirePayload:
    """One codec'd slab in its on-the-wire form, kept encoded end to end.

    The fused wire path (kernels/wire_path) consumes this directly: the
    receiving shard's kernel dequantizes in VMEM instead of the link
    model round-tripping to f32 at the hop.  ``payload`` is the flat
    (N,) slab in wire dtype (f32 / bf16 / int8); ``scale`` is the (C,)
    per-chunk f32 scale vector for the int8 codec, ``None`` otherwise.

    Invariant: ``decode_wire`` of this payload is bit-identical to what
    ``roundtrip`` would have returned for the same slab and error-feedback
    state — the wire form carries exactly the information the decoded
    form had, so keeping bytes encoded across the hop changes nothing
    numerically (tests/test_wire_path.py asserts this).
    """

    codec: str
    payload: jax.Array
    scale: jax.Array | None = None


def encode_wire(
    cfg: CompressionConfig, slab: jax.Array, ef: jax.Array | None
) -> tuple[WirePayload, jax.Array | None]:
    """Encode one hop for wire-direct consumption: ``(WirePayload, new_ef)``.

    Error feedback is updated exactly as ``roundtrip`` updates it (the
    sender's NIC/switch must know what the receiver will decode, so the
    residual still costs a local dequantize for int8); only the *shipped*
    form differs — the payload stays encoded for the fused kernel instead
    of crossing the hop as decoded f32.
    """
    if cfg.codec == "none":
        return WirePayload("none", slab), ef
    use_ef = cfg.error_feedback and ef is not None
    if use_ef:
        slab = slab + ef
    if cfg.codec == "bf16":
        wire = slab.astype(jnp.bfloat16)
        new_ef = (slab - wire.astype(jnp.float32)) if use_ef else ef
        return WirePayload("bf16", wire), new_ef
    if cfg.codec == "int8":
        q, scale = quantize_chunks(
            slab, cfg.chunk_elems, use_pallas=cfg.use_pallas
        )
        if use_ef:
            dec = dequantize_chunks(
                q, scale, cfg.chunk_elems, use_pallas=cfg.use_pallas,
            )
            new_ef = slab - dec
        else:
            new_ef = ef
        return WirePayload("int8", q, scale), new_ef
    raise ValueError(cfg.codec)


def decode_wire(cfg: CompressionConfig, wp: WirePayload) -> jax.Array:
    """Decode a ``WirePayload`` to f32 — the receiving end of the hop.

    Matches the fused kernel's in-VMEM decode bit-for-bit (same dequant
    expression); the fabric's unfused fallback and tests use it as the
    wire-form oracle."""
    if wp.codec == "none":
        return wp.payload
    if wp.codec == "bf16":
        return wp.payload.astype(jnp.float32)
    if wp.codec == "int8":
        return dequantize_chunks(
            wp.payload, wp.scale, cfg.chunk_elems, use_pallas=cfg.use_pallas,
        )
    raise ValueError(wp.codec)


def decode(cfg: CompressionConfig, payload: tuple) -> jax.Array:
    """Decode an ``encode`` payload tuple back to an (N,) f32 slab.

    Tuple-shaped counterpart of ``decode_wire`` (which takes the
    self-describing ``WirePayload``); both apply the identical dequant
    expression, so either can serve as the wire-form oracle."""
    if cfg.codec == "none":
        return payload[0]
    if cfg.codec == "bf16":
        return payload[0].astype(jnp.float32)
    if cfg.codec == "int8":
        q, scale = payload
        return dequantize_chunks(
            q, scale, cfg.chunk_elems, use_pallas=cfg.use_pallas
        )
    raise ValueError(cfg.codec)


def roundtrip(
    cfg: CompressionConfig, slab: jax.Array, ef: jax.Array | None
) -> tuple[jax.Array, jax.Array | None]:
    """Encode then immediately decode one hop: what the receiving end of a
    codec'd link sees, plus the sender's updated error-feedback state.

    This is the numeric model of one wire crossing (worker NIC -> ToR, or
    ToR -> core); byte accounting is separate (``wire_bytes``).  Unlike
    ``encode`` + ``decode`` — where the EF residual forces a second
    dequantize of the same payload — the decoded view is computed once and
    shared with the residual (bit-identical results, half the decode
    kernel invocations on the int8 path)."""
    if cfg.codec == "none":
        return slab, ef
    use_ef = cfg.error_feedback and ef is not None
    if use_ef:
        slab = slab + ef
    if cfg.codec == "bf16":
        dec = slab.astype(jnp.bfloat16).astype(jnp.float32)
    elif cfg.codec == "int8":
        q, scale = quantize_chunks(
            slab, cfg.chunk_elems, use_pallas=cfg.use_pallas
        )
        dec = dequantize_chunks(
            q, scale, cfg.chunk_elems, use_pallas=cfg.use_pallas,
        )
    else:
        raise ValueError(cfg.codec)
    return dec, (slab - dec) if use_ef else ef


def init_ef_state(cfg: CompressionConfig, n: int) -> jax.Array | None:
    """Zero error-feedback residual for an ``n``-element slab, or ``None``.

    ``None`` means the codec/config pair never accumulates a residual
    (codec "none", or error feedback disabled) — callers thread the value
    straight back into ``encode``/``roundtrip``."""
    if cfg.codec in ("int8", "bf16") and cfg.error_feedback:
        return jnp.zeros((n,), jnp.float32)
    return None
