"""Block quantization formats (Q40 / Q80), TPU-native layout.

Byte-compatible with the reference `.m` tensor encoding (reference: src/quants.hpp:17-25,
src/quants.cpp:137-288, converter/writer.py:29-74) but stored on device as *planar* arrays
instead of 18/34-byte interleaved structs:

    Q40 tensor of shape (rows, n):  packed uint8 (rows, n//32, 16)  + scales f16 (rows, n//32)
    Q80 tensor of shape (rows, n):  values int8  (rows, n//32, 32)  + scales f16 (rows, n//32)

Planar layout is what TPU wants: the packed nibbles land in HBM as a dense uint8 array that
Pallas kernels / XLA can tile onto (32, 128)-shaped int8 registers, while the f16 scales form
a small separate array that broadcasts over each 32-element block. The interleaved struct
layout of the reference exists only at file I/O boundaries (`*_to_bytes` / `*_from_bytes`).

Nibble semantics match the reference exactly (src/quants.cpp:178-182): byte j of a block
holds element j in its low nibble and element j+16 in its high nibble; value = (nibble-8)*d.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

QK = 32  # block size for both Q40 and Q80 (reference: src/quants.hpp:14-15)
Q40_BLOCK_BYTES = 18  # f16 delta + 16 nibble-pair bytes
Q80_BLOCK_BYTES = 34  # f16 delta + 32 int8

_Q40_STRUCT = np.dtype([("d", "<f2"), ("qs", "u1", (QK // 2,))])
_Q80_STRUCT = np.dtype([("d", "<f2"), ("qs", "i1", (QK,))])


class FloatType(enum.IntEnum):
    """Wire/storage float types (reference: src/quants.hpp:6-12)."""

    F32 = 0
    F16 = 1
    Q40 = 2
    Q80 = 3


def batch_bytes(ftype: FloatType, n: int, d: int = 1) -> int:
    """Bytes for a (d, n) tensor in the given storage type (reference: src/quants.cpp:28-51)."""
    count = n * d
    if ftype == FloatType.F32:
        return count * 4
    if ftype == FloatType.F16:
        return count * 2
    if ftype == FloatType.Q40:
        assert n % QK == 0, (n, d)
        return (count // QK) * Q40_BLOCK_BYTES
    if ftype == FloatType.Q80:
        assert n % QK == 0, (n, d)
        return (count // QK) * Q80_BLOCK_BYTES
    raise ValueError(f"unknown float type {ftype}")


# ---------------------------------------------------------------------------
# Q40: 4-bit blocks, asymmetric-ish (min/max) scaling with +8.5 offset
# ---------------------------------------------------------------------------


def quantize_q40(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Quantize float array (..., n) to Q40 planar (packed, scales).

    Matches converter/writer.py:29-53: delta = extremum/-8 in f16, q = clip(x/delta+8.5, 0, 15).

    Returns (packed uint8 (..., n//32, 16), scales float16 (..., n//32)).
    """
    x = np.asarray(x, dtype=np.float32)
    n = x.shape[-1]
    assert n % QK == 0, n
    g = x.reshape(*x.shape[:-1], n // QK, QK)
    gmax = g.max(axis=-1)
    gmin = g.min(axis=-1)
    deltas = np.where(-gmin > gmax, gmin, gmax) / -8.0
    deltas16 = deltas.astype(np.float16)
    inv = np.divide(1.0, deltas, out=np.zeros_like(deltas), where=deltas != 0).astype(np.float32)
    q = np.clip(g * inv[..., None] + 8.5, 0, 15).astype(np.uint8)
    packed = q[..., : QK // 2] | (q[..., QK // 2 :] << 4)
    return packed, deltas16


def dequantize_q40(packed: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Planar Q40 -> float32 (..., n). Matches src/quants.cpp:170-183."""
    lo = (packed & 0x0F).astype(np.int8) - 8
    hi = (packed >> 4).astype(np.int8) - 8
    vals = np.concatenate([lo, hi], axis=-1).astype(np.float32)
    out = vals * scales[..., None].astype(np.float32)
    return out.reshape(*packed.shape[:-2], packed.shape[-2] * QK)


def q40_to_bytes(packed: np.ndarray, scales: np.ndarray) -> bytes:
    """Planar Q40 -> reference interleaved block stream (BlockQ40[])."""
    nb = int(np.prod(packed.shape[:-1]))
    out = np.empty(nb, dtype=_Q40_STRUCT)
    out["d"] = scales.reshape(nb)
    out["qs"] = packed.reshape(nb, QK // 2)
    return out.tobytes()


def q40_from_bytes(buf: bytes, shape: tuple[int, ...]) -> tuple[np.ndarray, np.ndarray]:
    """Reference BlockQ40[] stream -> planar (packed, scales) for logical shape (..., n)."""
    n = shape[-1]
    assert n % QK == 0, shape
    nb_shape = (*shape[:-1], n // QK)
    nb = int(np.prod(nb_shape))
    from . import native

    nat = native.q40_deinterleave(buf, nb)
    if nat is not None:
        qs, d = nat
        return qs.reshape(*nb_shape, QK // 2), d.reshape(nb_shape)
    arr = np.frombuffer(buf, dtype=_Q40_STRUCT, count=nb)
    return arr["qs"].reshape(*nb_shape, QK // 2).copy(), arr["d"].reshape(nb_shape).copy()


# ---------------------------------------------------------------------------
# Q80: int8 blocks, symmetric absmax/127 scaling
# ---------------------------------------------------------------------------


def quantize_q80(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Quantize (..., n) to Q80 planar (values int8 (..., n//32, 32), scales f16 (..., n//32)).

    Matches converter/writer.py:55-74 / src/quants.cpp:186-268 (round-to-nearest-even).
    """
    x = np.asarray(x, dtype=np.float32)
    n = x.shape[-1]
    assert n % QK == 0, n
    g = x.reshape(*x.shape[:-1], n // QK, QK)
    absmax = np.abs(g).max(axis=-1)
    deltas = absmax / 127.0
    deltas16 = deltas.astype(np.float16)
    inv = np.divide(1.0, deltas, out=np.zeros_like(deltas), where=deltas != 0).astype(np.float32)
    q = np.round(g * inv[..., None]).astype(np.int8)
    return q, deltas16


def dequantize_q80(values: np.ndarray, scales: np.ndarray) -> np.ndarray:
    out = values.astype(np.float32) * scales[..., None].astype(np.float32)
    return out.reshape(*values.shape[:-2], values.shape[-2] * QK)


def q80_to_bytes(values: np.ndarray, scales: np.ndarray) -> bytes:
    nb = int(np.prod(values.shape[:-1]))
    out = np.empty(nb, dtype=_Q80_STRUCT)
    out["d"] = scales.reshape(nb)
    out["qs"] = values.reshape(nb, QK)
    return out.tobytes()


def q80_from_bytes(buf: bytes, shape: tuple[int, ...]) -> tuple[np.ndarray, np.ndarray]:
    n = shape[-1]
    assert n % QK == 0, shape
    nb_shape = (*shape[:-1], n // QK)
    nb = int(np.prod(nb_shape))
    from . import native

    nat = native.q80_deinterleave(buf, nb)
    if nat is not None:
        qs, d = nat
        return qs.reshape(*nb_shape, QK), d.reshape(nb_shape)
    arr = np.frombuffer(buf, dtype=_Q80_STRUCT, count=nb)
    return arr["qs"].reshape(*nb_shape, QK).copy(), arr["d"].reshape(nb_shape).copy()


# ---------------------------------------------------------------------------
# On-device (jnp) dequantization — the XLA-path used outside Pallas kernels
# ---------------------------------------------------------------------------


def jnp_dequantize_q40(packed: jax.Array, scales: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize planar Q40 on device: (..., nb, 16) u8 + (..., nb) f16 -> (..., nb*32)."""
    lo = (packed & 0x0F).astype(jnp.int8) - 8
    hi = (packed >> 4).astype(jnp.int8) - 8
    vals = jnp.concatenate([lo, hi], axis=-1).astype(dtype)
    out = vals * scales[..., None].astype(dtype)
    return out.reshape(*packed.shape[:-2], packed.shape[-2] * QK)


def jnp_dequantize_i8(values: jax.Array, scales: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize the int8-plane layout: (..., K) i8 + (..., K//32) f32 -> (..., K).

    Same math as Q80 planar dequant after regrouping the flat K axis into blocks.
    """
    k = values.shape[-1]
    nb = scales.shape[-1]
    assert nb * QK == k, (values.shape, scales.shape)
    return jnp_dequantize_q80(values.reshape(*values.shape[:-1], nb, QK), scales, dtype)


def jnp_dequantize_q80(values: jax.Array, scales: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    out = values.astype(dtype) * scales[..., None].astype(dtype)
    return out.reshape(*values.shape[:-2], values.shape[-2] * QK)


def jnp_quantize_q80(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """On-device Q80 quantization (..., n) -> (int8 (..., nb, 32), f16 scales).

    TPU-native descendant of the reference's wire compression (src/tasks.cpp:96-135):
    used for int8-compressed collectives instead of socket payloads.
    """
    n = x.shape[-1]
    g = x.reshape(*x.shape[:-1], n // QK, QK).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(g), axis=-1)
    deltas = (absmax / 127.0).astype(jnp.float16)
    inv = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
    q = jnp.round(g * inv[..., None]).astype(jnp.int8)
    return q, deltas


SCALE_LANES = 128  # columns of the chip's lane tile


def scale_plane_cols(nb: int, groups: int = 1) -> int:
    """Columns of the i4p scale plane of nb blocks a row: each of `groups`
    column groups' own nb / groups padded to whole lane tiles."""
    per = nb // groups
    return groups * (per + -per % SCALE_LANES)


def to_scale_plane(scales, groups: int = 1):
    """(..., nb) block scales -> the plane the kernels read, (...,
    scale_plane_cols(nb, groups)): zero columns behind each column group's
    own, so that the minor dimension is whole lane tiles. The chip keeps
    such an array row-major as stored and a kernel's (bn, columns) block
    reads it in place; a narrower plane (K/32 of 24, 80, 448) it keeps with
    the ROWS minor, and every step program then re-laid the whole stack,
    padded just so, before its first layer (PERF.md section 6, PR 46).
    NumPy in, NumPy out; a device array stays on the device. A plane that is
    whole lane tiles already comes back as it is."""
    nb = scales.shape[-1]
    cols = scale_plane_cols(nb, groups)
    if cols == nb:
        return scales
    xp = np if isinstance(scales, np.ndarray) else jnp
    s = scales.reshape(*scales.shape[:-1], groups, nb // groups)
    s = xp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, (cols - nb) // groups)])
    return s.reshape(*scales.shape[:-1], cols)


def jnp_to_i4p(packed: jax.Array, scales: jax.Array, col_groups: int = 1
               ) -> tuple[jax.Array, jax.Array]:
    """`QTensor.to_i4p_layout` on the device, bit for bit: planar Q40 bytes
    (..., nb, 16), or the same flattened to (..., K/2), with f16 scales ->
    split-plane nibbles (..., K/2) and the scales' int16 bit patterns as the
    plane the kernels read (`to_scale_plane`).

    Within a column group the low plane is blocks 0 .. nbg/2 and the high
    plane the rest. Output block b of a group (32 bytes) takes its low
    nibbles from planar block b and its high nibbles from planar block
    b + nbg/2: bytes 0..15 their low nibbles (block elements 0..15), bytes
    16..31 their high nibbles (elements 16..31). Nothing is widened past
    uint8 and no nibble is unpacked to a byte of its own."""
    k2 = packed.shape[-1] * (packed.shape[-2] if packed.ndim == scales.ndim + 1
                             else 1)
    lead = scales.shape[:-1]
    assert k2 == scales.shape[-1] * (QK // 2), (packed.shape, scales.shape)
    assert k2 % col_groups == 0 and (k2 // col_groups) % QK == 0, (
        k2 * 2, col_groups)
    p = packed.reshape(*lead, col_groups, 2, k2 // col_groups // QK, QK // 2)
    a, b = p[..., 0, :, :], p[..., 1, :, :]
    lo = (a & 0x0F) | ((b & 0x0F) << 4)
    hi = (a >> 4) | (b & 0xF0)
    data = jnp.stack([lo, hi], axis=-2).reshape(*lead, k2)
    return data, to_scale_plane(
        jax.lax.bitcast_convert_type(scales, jnp.int16), col_groups)


# ---------------------------------------------------------------------------
# QTensor: a quantized-or-not weight tensor as a pytree
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor:
    """A weight tensor, stored dense or block-quantized.

    For Q40/Q80 the block axis is the LAST logical axis (the contraction axis `n` of the
    reference's (d, n) row-major weights; reference blocks run along n — src/commands.cpp:22-39).
    Registered as a pytree so QTensors flow through jit/scan/shard_map and can carry per-leaf
    shardings. `shape` is derived from `data`, so it stays correct when transforms (scan
    unstacking, vmap, gathers) reshape the leaves.
    """

    ftype: FloatType
    data: jax.Array | np.ndarray  # dense values, Q40 packed u8, or Q80 int8
    # per-block scales for Q40/Q80: f16 (planar), f32 (i8), int16 f16-bit-patterns
    # in whole lane tiles a column group (i4p, `to_scale_plane`)
    scales: jax.Array | np.ndarray | None = None
    # "planar" | "i8" (int8 planes, to_i8_layout) | "i4p" (split-plane packed nibbles,
    # to_i4p_layout — true Q40 HBM density for the pallas_q4 decode kernel)
    layout: str = "planar"
    # i4p only: number of column groups the split-plane pack was applied within
    # (= the TP degree for in-axis-sharded tensors, so each shard's slice is a
    # self-contained pack). 1 elsewhere.
    groups: int = 1
    # fused matvec groups only (models/params.py fuse_matvec_groups): the
    # TP-group count the member ROWS were interleaved with at fuse time. Carried
    # through layout conversion so shard time can verify the placement matches
    # the interleave (a mismatch would silently scramble the member split). 1
    # for unfused tensors.
    row_groups: int = 1

    @property
    def shape(self) -> tuple[int, ...]:
        """Logical (dequantized) shape."""
        if self.ftype in (FloatType.F32, FloatType.F16):
            return tuple(self.data.shape)
        if self.layout == "i8":
            return tuple(self.data.shape)
        if self.layout == "i4p":
            return (*self.data.shape[:-1], self.data.shape[-1] * 2)
        if self.ftype in (FloatType.Q40, FloatType.Q80):
            return (*self.data.shape[:-2], self.data.shape[-2] * QK)
        raise ValueError(self.ftype)

    def tree_flatten(self):
        aux = (self.ftype, self.scales is not None, self.layout, self.groups,
               self.row_groups)
        if self.scales is None:
            return (self.data,), aux
        return (self.data, self.scales), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        ftype, has_scales, layout, groups, row_groups = aux
        if has_scales:
            data, scales = children
        else:
            (data,) = children
            scales = None
        return cls(ftype=ftype, data=data, scales=scales, layout=layout,
                   groups=groups, row_groups=row_groups)

    def to_i8_layout(self) -> "QTensor":
        """Expand planar Q40/Q80 into int8 planes for the MXU matvec kernel (pallas_q8).

        data int8 (..., K) holding (nibble - 8) for Q40 / raw int8 for Q80, natural
        column order; scales f32 (..., K//32). Costs 2x (Q40) the packed HBM bytes but
        removes every per-weight VPU op from decode; both axes slice cleanly for TP
        (blocks stay 32-aligned), so no per-shard segmenting is needed.
        """
        assert self.layout == "planar", self.layout
        if self.ftype == FloatType.Q40:
            from . import native

            nat = native.q40_to_i8(np.asarray(self.data), np.asarray(self.scales))
            if nat is not None:
                return QTensor(self.ftype, nat[0], nat[1], layout="i8",
                               row_groups=self.row_groups)
            packed = np.asarray(self.data)
            lo = (packed & 0x0F).astype(np.int8) - 8  # elements 0..15 of each block
            hi = (packed >> 4).astype(np.int8) - 8  # elements 16..31
            vals = np.concatenate([lo, hi], axis=-1)  # (..., nb, 32)
        elif self.ftype == FloatType.Q80:
            vals = np.asarray(self.data, dtype=np.int8)
        else:
            raise ValueError(self.ftype)
        k = vals.shape[-2] * QK
        data = vals.reshape(*vals.shape[:-2], k)
        scales32 = np.asarray(self.scales, dtype=np.float32)
        return QTensor(self.ftype, data, scales32, layout="i8",
                       row_groups=self.row_groups)

    def to_i4p_layout(self, col_groups: int = 1) -> "QTensor":
        """Repack planar Q40 into split-plane nibbles for the 4-bit MXU matvec kernel
        (ops/pallas_q4.py): data uint8 (..., K/2) with byte j = q[j] | (q[j+K/2] << 4)
        where q = nibble+8; scales stored as int16 BIT PATTERNS of the file's f16
        deltas (bit-exact, same 2 B/block) because Mosaic on this toolchain cannot
        lower f16 refs — the kernel decodes f16-bits -> f32 with exact integer math
        (pallas_q4._f16_bits_to_f32) and dequantize()/to_numpy() bitcast back.
        They are STORED as the plane the kernels' blocks read in place,
        (..., N, scale_plane_cols(K/32, col_groups)): each column group's K/32
        scales with zero columns behind them up to whole 128-lane tiles
        (`to_scale_plane`; K/32 = 128 stays 128, 24 and 80 become 128, 448
        becomes 512). `block_scales` gives the file's (..., N, K/32) back.

        Both unpacked planes land in natural element order, so the kernel needs no
        cross-lane shuffles. Same HBM bytes as the reference's BlockQ40 stream
        (src/quants.hpp:17-20).

        col_groups: split-plane pack WITHIN each of `col_groups` equal column groups —
        required for in-axis (ColMatmulSlice) TP sharding, where each shard must receive
        a self-contained split-plane pack of its own K/col_groups columns. Row-sharded
        tensors use col_groups=1. Each group's K_local must satisfy K_local % 64 == 0
        so the plane boundary stays on a quant-block boundary.
        """
        assert self.layout == "planar" and self.ftype == FloatType.Q40, (
            self.layout, self.ftype)
        packed = np.asarray(self.data)  # (..., nb, 16)
        from . import native

        scales16 = to_scale_plane(np.ascontiguousarray(
            np.asarray(self.scales, dtype=np.float16)).view(np.int16),
            col_groups)
        nat = native.q40_to_i4p(packed, col_groups)
        if nat is not None:
            return QTensor(self.ftype, nat, scales16, layout="i4p",
                           groups=col_groups, row_groups=self.row_groups)
        lo = (packed & 0x0F).astype(np.uint8)  # block elements 0..15
        hi = (packed >> 4).astype(np.uint8)  # block elements 16..31
        q = np.concatenate([lo, hi], axis=-1)  # (..., nb, 32) natural order, in [0,16)
        k = q.shape[-2] * QK
        lead = q.shape[:-2]
        kl = k // col_groups
        assert k % col_groups == 0 and kl % 64 == 0, (k, col_groups)
        q = q.reshape(*lead, col_groups, kl)
        data = q[..., : kl // 2] | (q[..., kl // 2 :] << 4)
        data = data.reshape(*lead, k // 2)
        return QTensor(self.ftype, data, scales16, layout="i4p",
                       groups=col_groups, row_groups=self.row_groups)

    def block_scales(self):
        """An i4p tensor's scales less the plane's padding: (..., N, K/32)
        int16 bit patterns in the file's block order."""
        assert self.layout == "i4p", self.layout
        nb = self.data.shape[-1] * 2 // QK
        s, g = self.scales, self.groups
        assert s.shape[-1] == scale_plane_cols(nb, g), (s.shape, nb, g)
        if s.shape[-1] == nb:
            return s
        s = s.reshape(*s.shape[:-1], g, s.shape[-1] // g)[..., :nb // g]
        return s.reshape(*s.shape[:-2], nb)

    def _i4p_unpack(self, xp):
        """Split-plane nibbles -> natural-order values (..., K) minus the 8 offset."""
        wp = self.data
        kh = wp.shape[-1]
        g = self.groups
        wp = wp.reshape(*wp.shape[:-1], g, kh // g)
        lo = xp.asarray((wp & 0x0F), dtype=xp.int8) - 8
        hi = xp.asarray((wp >> 4), dtype=xp.int8) - 8
        out = xp.concatenate([lo, hi], axis=-1)  # (..., g, K/g) natural within group
        return out.reshape(*out.shape[:-2], kh * 2)

    @classmethod
    def from_float(cls, x: np.ndarray, ftype: FloatType) -> "QTensor":
        x = np.asarray(x)
        if ftype == FloatType.F32:
            return cls(ftype, x.astype(np.float32))
        if ftype == FloatType.F16:
            return cls(ftype, x.astype(np.float16))
        if ftype == FloatType.Q40:
            packed, scales = quantize_q40(x)
            return cls(ftype, packed, scales)
        if ftype == FloatType.Q80:
            vals, scales = quantize_q80(x)
            return cls(ftype, vals, scales)
        raise ValueError(ftype)

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        """Materialize logical values on device (jnp path; Pallas kernels bypass this)."""
        if self.ftype in (FloatType.F32, FloatType.F16):
            return jnp.asarray(self.data).astype(dtype)
        if self.layout == "i8":
            return jnp_dequantize_i8(jnp.asarray(self.data), jnp.asarray(self.scales),
                                     dtype)
        if self.layout == "i4p":
            vals = self._i4p_unpack(jnp)
            scales = jax.lax.bitcast_convert_type(
                jnp.asarray(self.block_scales()), jnp.float16)
            g = vals.reshape(*vals.shape[:-1], scales.shape[-1], QK)
            return jnp_dequantize_q80(g, scales, dtype)
        if self.ftype == FloatType.Q40:
            return jnp_dequantize_q40(jnp.asarray(self.data), jnp.asarray(self.scales), dtype)
        if self.ftype == FloatType.Q80:
            return jnp_dequantize_q80(jnp.asarray(self.data), jnp.asarray(self.scales), dtype)
        raise ValueError(self.ftype)

    def to_numpy(self) -> np.ndarray:
        if self.ftype in (FloatType.F32, FloatType.F16):
            return np.asarray(self.data, dtype=np.float32)
        if self.layout == "i8":
            nb = self.scales.shape[-1]
            g = np.asarray(self.data).reshape(*self.data.shape[:-1], nb, QK)
            return dequantize_q80(g, np.asarray(self.scales))
        if self.layout == "i4p":
            vals = self._i4p_unpack(np)
            scales = np.ascontiguousarray(self.block_scales())
            g = vals.reshape(*vals.shape[:-1], scales.shape[-1], QK)
            return dequantize_q80(g, scales.view(np.float16))
        if self.ftype == FloatType.Q40:
            return dequantize_q40(np.asarray(self.data), np.asarray(self.scales))
        if self.ftype == FloatType.Q80:
            return dequantize_q80(np.asarray(self.data), np.asarray(self.scales))
        raise ValueError(self.ftype)

    def nbytes(self) -> int:
        n = self.data.nbytes
        if self.scales is not None:
            n += self.scales.nbytes
        return n
