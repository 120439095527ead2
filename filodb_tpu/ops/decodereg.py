"""Shared decode-variant registry for the fused compressed-resident tier.

Reference role: the reference FiloDB reads every chunk through ONE codec
dispatch table (format/vectors/*.scala — each vector type names its reader
and the iterator chain decodes on access). This module is the TPU analog:
every narrow-resident block format the fused kernels can stream is a
registered :class:`DecodeVariant` naming BOTH backend decode twins — the
``pallas`` one the kernel body calls on its VMEM refs and the ``xla`` one
the scan twin calls on its tile slices. Both compute the same exact
integer arithmetic (where Mosaic lacks a primitive the Pallas twin spells
it with what the chip has), so variant parity holds bit for bit; filolint's
``surface-decode-variant-twin`` rule makes one-sided additions (a variant
registered with only one backend) fail tier-1.

Variants registered here:

  name     block dtype  row operands      decode
  -------  -----------  ----------------  ---------------------------------
  raw      f32 [S,C]    —                 identity
  quant16  i16 [S,C]    vmin, scale       vmin + (q + 32768) * scale
  delta16  i16 [S,C]    anchor            anchor + cumsum(dv)  (full cols;
  delta8   i8  [S,C]    anchor              Pallas: prefix sum on the MXU)
  hist16   i16 [S,C,B]  first_d           dd -> f32 (cumsums in tile math)
  hist8    i8  [S,C,B]  first_d           dd -> f32 (cumsums in tile math)

``full_columns`` marks variants whose decode needs the whole column prefix
(the delta cumsum telescopes from cell 0), so the active-column slicing of
ops/fusedgrid.active_columns must be bypassed — same constraint the hist
tier documents in hist_fusable. ``value_bytes`` is the per-sample block
cost the residency accounting and the bench suite report.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DecodeVariant:
    """One narrow block format both fused backends can stream.

    ``pallas``/``xla`` map (block, *row_operands) -> decoded f32 values;
    the kernel body calls ``pallas`` on materialized VMEM refs, the scan
    twin calls ``xla`` on its per-tile slices. ``row_operands`` counts the
    per-row f32 side arrays the decode consumes beyond the block itself
    (``[S]`` in the store; a ``[Sb, 1]`` column of the tile by the time
    the decode sees them, turned inside the fused program:
    ops/fusedgrid.py ``lane_major``)."""

    name: str
    pallas: Callable
    xla: Callable
    row_operands: int
    block_dtype: str
    full_columns: bool
    value_bytes: int


DECODE_VARIANTS: dict[str, DecodeVariant] = {}

# scalar variants eligible on 2-D [S, C] stores (fusedgrid tier); hist
# variants ride the [S, C, B] tier in ops/fusedresident.py
SCALAR_VARIANTS = ("quant16", "delta16", "delta8")


def register_variant(name: str, *, pallas: Callable, xla: Callable,
                     row_operands: int, block_dtype: str,
                     full_columns: bool, value_bytes: int) -> DecodeVariant:
    """Register a decode variant. BOTH backend twins are required — a
    variant that only one backend can serve would silently fall back when
    ``query.fused_kernels`` selects the other, breaking the variant-parity
    contract (and filolint's surface-decode-variant-twin rule enforces the
    call-site shape statically)."""
    if pallas is None or xla is None:
        raise ValueError(f"decode variant {name!r} must declare both a "
                         "pallas and an xla twin")
    if name in DECODE_VARIANTS:
        raise ValueError(f"decode variant {name!r} already registered")
    v = DecodeVariant(name, pallas, xla, row_operands, block_dtype,
                      full_columns, value_bytes)
    DECODE_VARIANTS[name] = v
    return v


def variant(name: str) -> DecodeVariant:
    return DECODE_VARIANTS[name]


# ---------------------------------------------------------------------------
# decode twins — plain jnp expressions valid both inside a Pallas body (on
# values read from VMEM refs) and inside the XLA scan (on tile slices)
# ---------------------------------------------------------------------------

def decode_raw(v):
    """Raw f32 block: identity."""
    return v


def decode_quant16(q, vmin, scale):
    """u16 quantized mirror decode (ops/narrow.build_narrow): the biased
    i16 block stores x = q - 32768 for q = round((v - vmin)/2^e) in
    [0, 65535]; q * 2^e is exact (q < 2^16, power-of-two scale) and
    vmin + q * 2^e reproduces the f32 value bit-exactly for rows the
    encoder verified — HALF the HBM bytes of the raw f32 stream (ref: the
    reference decompresses NibblePack chunks on access for the same
    bandwidth reason). Integers <= 65535 are exact in f32."""
    return vmin + (q.astype(jnp.float32) + 32768.0) * scale


def decode_delta(dv, anchor):
    """Scalar delta decode (ops/narrow.build_narrow_delta): each row is a
    f32 anchor plus i16/i8 per-step value deltas; the prefix sum rebuilds
    the exact value sequence in VMEM (encoder verified |prefix| <= 2^23 so
    every partial sum is integer-exact in f32). Needs the FULL column
    prefix — variants using this are registered full_columns and bypass
    active-column slicing. 1-2 bytes/sample vs the raw 4."""
    return anchor + jnp.cumsum(dv.astype(jnp.float32), axis=1)


_LANE_BLOCK = 128


def _cumsum_lanes_mxu(x):
    """Prefix sum along the minor (lane) axis of ``x [Sb, C]`` — bf16
    holding small exact integers — as 128-wide triangular matmuls on the
    MXU plus a running per-row carry. Mosaic has no cumsum primitive
    (the TPU compiler's words: "Unimplemented primitive in Pallas TPU
    lowering ... cumsum"). bf16 operands x 0/1 weights with f32
    accumulation are exact at ANY matmul precision, so the result equals
    jnp.cumsum's bit for bit while every prefix stays below 2^24."""
    Sb, C = x.shape
    lb = min(_LANE_BLOCK, C)
    r = jax.lax.broadcasted_iota(jnp.int32, (lb, lb), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (lb, lb), 1)
    tri = (r <= c).astype(jnp.bfloat16)
    outs, carry = [], None
    for k0 in range(0, C, lb):
        w = min(lb, C - k0)
        # DEFAULT, spelled out: the package-wide "highest" would ask Mosaic
        # for an fp32 contraction of bf16 operands ("Bad lhs type")
        blk = jnp.dot(x[:, k0:k0 + w], tri[:w, :w],
                      precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)
        if carry is not None:
            blk = blk + carry
        carry = blk[:, w - 1:w]
        outs.append(blk)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def decode_delta_mxu(dv, anchor):
    """The Pallas twin of :func:`decode_delta`: the same value sequence
    with the prefix sum on the MXU. i8 deltas are exact in bf16 as they
    are; i16 deltas split into a signed high byte and an unsigned low byte
    (both exact in bf16), summed separately and recombined — all integer
    arithmetic below 2^24, so the twins agree bit for bit."""
    d = dv.astype(jnp.int32)
    if dv.dtype == jnp.int8:
        pre = _cumsum_lanes_mxu(d.astype(jnp.float32).astype(jnp.bfloat16))
    else:
        hi = (d >> 8).astype(jnp.float32).astype(jnp.bfloat16)
        lo = (d & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        pre = _cumsum_lanes_mxu(hi) * 256.0 + _cumsum_lanes_mxu(lo)
    return anchor + pre


def decode_hist(dd, first_d):
    """Hist 2D-delta widen: the per-series math (hist_series_contrib)
    consumes the narrow dd frames directly — its band matmuls and bucket
    cumsums ARE the decode — so the decode step is just the i8/i16 -> f32
    cast. first_d rides as a row operand into the same math."""
    return dd.astype(jnp.float32)


register_variant("raw", pallas=decode_raw, xla=decode_raw,
                 row_operands=0, block_dtype="float32",
                 full_columns=False, value_bytes=4)
register_variant("quant16", pallas=decode_quant16, xla=decode_quant16,
                 row_operands=2, block_dtype="int16",
                 full_columns=False, value_bytes=2)
register_variant("delta16", pallas=decode_delta_mxu, xla=decode_delta,
                 row_operands=1, block_dtype="int16",
                 full_columns=True, value_bytes=2)
register_variant("delta8", pallas=decode_delta_mxu, xla=decode_delta,
                 row_operands=1, block_dtype="int8",
                 full_columns=True, value_bytes=1)
register_variant("hist16", pallas=decode_hist, xla=decode_hist,
                 row_operands=1, block_dtype="int16",
                 full_columns=True, value_bytes=2)
register_variant("hist8", pallas=decode_hist, xla=decode_hist,
                 row_operands=1, block_dtype="int8",
                 full_columns=True, value_bytes=1)
