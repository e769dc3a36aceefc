"""Shared helpers for the Pallas TPU kernels.

TPU target, CPU-validated: kernels are written against the TPU memory
hierarchy (HBM -> VMEM BlockSpecs -> MXU/VPU) and validated on CPU with
``interpret=True``. ``use_interpret()`` auto-selects interpret mode when no
TPU is present so the same call sites run everywhere.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# MXU native tile: 128x128 systolic; VPU lanes (8, 128). A block's last two
# dims must be multiples of (SUBLANE, MXU) or span the whole array axis.
MXU = 128
SUBLANE = 8


def use_interpret() -> bool:
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "auto")
    if env in ("1", "true"):
        return True
    if env in ("0", "false"):
        return False
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """Resolve a kernel wrapper's ``interpret`` argument: ``None`` (the
    default everywhere) means backend-detected — compiled on TPU, interpreter
    elsewhere (and whatever REPRO_PALLAS_INTERPRET forces, which is how CI
    pins interpret mode). An explicit bool always wins, so benchmarks can
    still measure the interpreter deliberately."""
    return use_interpret() if interpret is None else bool(interpret)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_dim(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad one axis up to a multiple (wrapper-level tile alignment)."""
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def pick_block(dim: int, preferred: int) -> int:
    """Largest power-of-two block <= preferred that is used with a cdiv grid
    + wrapper padding, so any dim works while MXU-aligned dims stay aligned."""
    b = min(preferred, dim)
    # round up small dims to themselves; keep pow2-ish blocks otherwise
    p = 1
    while p * 2 <= b:
        p *= 2
    return p if dim >= preferred else round_up(dim, SUBLANE) if dim % SUBLANE else dim


def acc_dtype(dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def mxu_dot(a: jax.Array, b: jax.Array, adt) -> jax.Array:
    """One MXU contraction accumulating at ``adt``. Integer operands enter
    the MXU as int8 — the v5e MXU has no int32 matmul, and int8 x int8 ->
    int32 is exact (W4 codes after ``shift_w4_block`` fit int8 by the
    quantizer's shift clamp). Float operands are widened to ``adt``."""
    if jnp.issubdtype(adt, jnp.integer):
        a, b = a.astype(jnp.int8), b.astype(jnp.int8)
    else:
        a, b = a.astype(adt), b.astype(adt)
    return jnp.dot(a, b, preferred_element_type=adt)


def apply_requant(acc: jax.Array, requant_shift: int | None) -> jax.Array:
    """Algorithm-1 epilogue on an int32 accumulator: round-to-nearest
    arithmetic shift to the output scale, clipped to the int8 range.

    The shift IS ``core.quantize.rshift_round`` (one implementation, so the
    Pallas kernel epilogues, the jnp oracles in ``kernels/ref.py``, and the
    host-side requantization are bit-exact by construction).
    ``requant_shift`` may be negative (pure left shift, exact) or ``None``
    (no-op, float paths).
    """
    if requant_shift is None:
        return acc
    from repro.core.quantize import rshift_round
    return jnp.clip(rshift_round(acc, requant_shift), -128, 127)


def apply_act(acc: jax.Array, act: str | None) -> jax.Array:
    """Fused activation epilogue, applied at ACCUMULATOR scale (int32/f32),
    i.e. before ``apply_requant``. Requantization is a monotonic shift with
    ``rshift_round(0) == 0``, so ``relu`` before the shift is bit-exact with
    relu on the requantized int8 (and with float relu after dequantization)
    — which is what lets the graph executor fuse the whole
    conv+BN+ReLU block into one kernel with zero float round-trips.
    """
    if act is None:
        return acc
    if act == "relu":
        return jnp.maximum(acc, 0)
    raise ValueError(f"unknown act {act!r}; expected 'relu' or None")


def resolve_tile_config(config, block_n: int, block_h, block_w):
    """Overlay a repro.tune schedule dict onto a kernel wrapper's
    (block_n, block_h, block_w) arguments — the one place the tiled-grid
    knobs are parsed, so every kernel stays in sync with the tuner's space
    (falsy/absent spatial blocks mean "whole extent")."""
    if config:
        block_n = int(config.get("block_n", block_n))
        block_h = int(config["block_h"]) if config.get("block_h") else block_h
        block_w = int(config["block_w"]) if config.get("block_w") else block_w
    return block_n, block_h, block_w


def batch_spatial_schedule(n: int, h: int, w: int, block_n: int,
                           block_h, block_w):
    """Resolve the (batch_block, spatial_tile) half of the tiled conv grid.

    ``block_n`` degrades to the largest divisor of the batch (the executor's
    pow2 batch buckets make this exact in practice); ``block_h``/``block_w``
    clamp to the output extent and grid with cdiv + wrapper padding, so odd
    feature maps get ragged final tiles instead of degenerate 1-row blocks.
    ``None`` spatial blocks mean "whole extent" (the untiled pre-batching
    schedule). Returns ``(bn, bh, bw, n_th, n_tw)``.
    """
    bn = effective_block(n, max(1, int(block_n)))
    bh = max(1, min(int(block_h) if block_h else h, h))
    bw = max(1, min(int(block_w) if block_w else w, w))
    return bn, bh, bw, cdiv(h, bh), cdiv(w, bw)


def halo_tiles(x: jax.Array, n_th: int, n_tw: int, step_h: int, step_w: int,
               size_h: int, size_w: int) -> jax.Array:
    """Overlapping spatial tile tensor for the halo-padded conv/pool grids:
    ``(N, Hp, Wp, C) -> (N, Th, Tw, size_h, size_w, C)`` where tile (i, j)
    is ``x[:, i*step_h : i*step_h+size_h, j*step_w : j*step_w+size_w]``.

    Pallas blocked BlockSpecs stride by the block shape, so halos cannot be
    expressed as overlapping blocks directly; instead the wrapper duplicates
    the ``size - step`` halo rows/cols once in HBM (overhead factor
    ``size/step`` per axis — small for the tile sizes the tuner picks) and
    the kernel grid indexes disjoint tiles. Bottom/right are zero-padded to
    full tiles; the padded region only feeds output rows the wrapper crops,
    so correctness never depends on the pad value. The untiled case
    (one tile covering everything) degenerates to a free reshape.
    """
    n, hp, wp, c = x.shape
    need_h = (n_th - 1) * step_h + size_h
    need_w = (n_tw - 1) * step_w + size_w
    if need_h > hp or need_w > wp:
        x = jnp.pad(x, ((0, 0), (0, max(0, need_h - hp)),
                        (0, max(0, need_w - wp)), (0, 0)))
    if n_th == 1 and n_tw == 1:
        return x[:, None, None, :size_h, :size_w, :]
    rows = jnp.stack([x[:, i * step_h:i * step_h + size_h]
                      for i in range(n_th)], axis=1)
    return jnp.stack([rows[:, :, :, j * step_w:j * step_w + size_w, :]
                      for j in range(n_tw)], axis=2)


def w4_nibble(wp: jax.Array, hi: int) -> jax.Array:
    """The sign-extended int32 codes in the low (``hi=0``, bits 0-3) or
    high (``hi=1``, bits 4-7) nibble of every byte of a W4-packed block."""
    return jnp.right_shift(jnp.left_shift(wp.astype(jnp.int32),
                                          24 if hi else 28), 28)


def w4_plane_order(size: int):
    """Channel order of :func:`w4_planes`: even indices, then odd ones."""
    import numpy as np
    return np.concatenate([np.arange(0, size, 2), np.arange(1, size, 2)])


def w4_planes(wp: jax.Array, size: int) -> jax.Array:
    """Unpack a W4 block packed along axis 0 into int32 codes in
    :func:`w4_plane_order` — the low nibbles (even codes), then the high
    ones (odd codes) — by one sublane concatenation. Cheaper to compile
    than :func:`unpack_w4_block`'s interleave; for a reduction whose other
    operand the wrapper permutes into the same order."""
    return jnp.concatenate([w4_nibble(wp, 0), w4_nibble(wp, 1)[:size // 2]],
                           axis=0)


def unpack_w4_block(wp: jax.Array, size: int, axis: int = 0) -> jax.Array:
    """In-register nibble unpack for a W4-packed weight block: int8 bytes
    holding two two's-complement int4 codes -> int32 codes, ``shape[axis]``
    going ``ceil(size/2) * 2 -> size``. Element ``2i`` is the low nibble of
    byte ``i`` (``core.quantize.pack_w4``'s layout). Runs inside kernel
    bodies on VPU registers, so the packed block is what crosses HBM->VMEM
    (the halved-weight-traffic contract); the arithmetic mirrors
    ``core.quantize.unpack_w4`` bit-for-bit. Zero bytes unpack to zero
    codes, so Pallas' zero-padded ragged blocks stay neutral."""
    axis = axis % wp.ndim
    out = jnp.stack([w4_nibble(wp, 0), w4_nibble(wp, 1)], axis=axis + 1)
    shape = list(wp.shape)
    shape[axis] = shape[axis] * 2
    out = out.reshape(shape)
    if out.shape[axis] == size:
        return out
    return jax.lax.slice_in_dim(out, 0, size, axis=axis)


def shift_w4_block(w4: jax.Array, ws: jax.Array, axis: int = 0) -> jax.Array:
    """Apply a W4 per-element group-scale shift vector along ``axis`` of an
    unpacked int32 code block: ``q4 << shift`` at the shared base scale —
    the in-kernel half of ``core.quantize.expand_w4``."""
    bshape = [1] * w4.ndim
    bshape[axis % w4.ndim] = ws.shape[-1]
    return jnp.left_shift(w4, ws.astype(jnp.int32).reshape(bshape))


def contraction_block(k: int, bk: int) -> int:
    """The K block the matmul actually runs: all of K when ``bk >= k``,
    else the largest lane-aligned (multiple of ``MXU``) divisor of ``k``
    that is <= ``bk``, else all of K. K blocks must tile K exactly: a
    ragged last K block would feed the accumulator whatever lies past the
    array's end (interpret mode pads with int-min, the chip with garbage).
    Shared by the kernel wrapper and the tuner's effective schedule."""
    if bk >= k:
        return k
    b = (bk // MXU) * MXU
    while b >= MXU:
        if k % b == 0:
            return b
        b -= MXU
    return k


def effective_block(dim: int, block: int) -> int:
    """The block size a divisor-gridded kernel actually runs: the largest
    divisor of ``dim`` that is <= ``block``. Single source of truth shared by
    the kernel wrappers, the tuner's search space, and its cost model — two
    configs with the same effective block are the same schedule."""
    b = max(1, min(block, dim))
    while dim % b:
        b -= 1
    return b
