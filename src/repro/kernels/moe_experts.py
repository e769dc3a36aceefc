"""Dropless grouped SwiGLU: each routed (token, expert) assignment computed
by its expert, for the mixture-of-experts layer of a served model.

The wrapper sorts the assignments by expert into a row buffer in which each
expert's rows start on a tile boundary, ``tm`` rows a tile, so a tile
belongs to one expert. The kernel's grid runs over (tile, chunk of the
expert width): per step it reads the tile's rows and one ``(tf, d)`` chunk
of each of the expert's three matrices, and accumulates
``(silu(x W_gate^T) * (x W_up^T)) W_down`` for that chunk in float32. The
tile -> expert map and the number of tiles in use are scalar prefetch;
grid steps past the tiles in use repeat the last tile's block indices, so
they move nothing and compute nothing.

Each expert's matrices are stored ``(E, ff, d)``, the expert width
leading, so a chunk of ``tf`` rows is one contiguous block of HBM. When
``tm`` covers the call's tokens (every decode round) no expert has more
than one tile: each routed expert's matrices are read once per call, and
an expert that no token chose is not read at all. Padding rows are zeros
and are never gathered back. No token is dropped or padded away: unlike a
capacity-factor dispatch, the result for a token does not depend on what
else is in the batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import MXU, SUBLANE, cdiv, resolve_interpret, round_up

_NT = (((1,), (1,)), ((), ()))          # x (tm, d) . w (tf, d)^T


def _kernel(tile_expert, n_tiles, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < n_tiles[0])
    def _():
        @pl.when(f == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        x = x_ref[...]
        g = lax.dot_general(x, wg_ref[...], _NT,
                            preferred_element_type=jnp.float32)
        u = lax.dot_general(x, wu_ref[...], _NT,
                            preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(wd_ref.dtype)
        o_ref[...] += jnp.dot(h, wd_ref[...],
                              preferred_element_type=jnp.float32)


def _block_ff(ff: int, block_ff: int) -> int:
    """The largest multiple of 128 that divides ``ff`` and is at most
    ``block_ff`` (``ff`` itself when none is)."""
    for tf in range(block_ff - block_ff % MXU, 0, -MXU):
        if ff % tf == 0:
            return tf
    return ff


def moe_experts(x: jax.Array, ids: jax.Array, weights: jax.Array,
                w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
                block_tokens: int = 256, block_ff: int = 256,
                interpret: bool | None = None):
    """``y[t] = sum_i weights[t, i] * E_{ids[t, i]}(x[t])`` with
    ``E_e(x) = (silu(x W_gate[e]^T) * (x W_up[e]^T)) W_down[e]``.

    x: (T, d); ids: (T, k) int32 experts, distinct within a row; weights:
    (T, k) float32; w_gate, w_up, w_down: (E, ff, d). Returns ``y`` (T, d)
    float32 and ``counts`` (E,) int32, the assignments each expert took.
    """
    tokens, d = x.shape
    n_exp, ff, _ = w_gate.shape
    k = ids.shape[1]
    a = tokens * k
    tm = min(round_up(tokens, 2 * SUBLANE), round_up(block_tokens,
                                                     2 * SUBLANE))
    tf = _block_ff(ff, block_ff)
    nf = ff // tf
    # static bound on the tiles in use: sum_e ceil(n_e / tm)
    n_max = min(n_exp, a) + a // tm
    flat = ids.reshape(-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(flat, n_exp, dtype=jnp.int32)
    counts = jnp.sum(onehot, axis=0)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               flat[:, None], axis=1)[:, 0]
    tiles = cdiv(counts, tm)
    ends = jnp.cumsum(tiles)
    rows = (ends - tiles)[flat] * tm + rank              # (a,)
    used = ends[-1]
    tile_expert = jnp.searchsorted(
        ends, jnp.minimum(jnp.arange(n_max), used - 1),
        side="right").astype(jnp.int32)
    xs = jnp.zeros((n_max * tm, d), w_gate.dtype).at[rows].set(
        jnp.repeat(x.astype(w_gate.dtype), k, axis=0))

    def rows_at(t, te, n):
        return jnp.minimum(t, n[0] - 1), 0

    def chunk_at(t, f, te, n):
        return te[t], jnp.where(t < n[0], f, nf - 1), 0

    wspec = pl.BlockSpec((None, tf, d), chunk_at)
    ys = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_max, nf),
            in_specs=[pl.BlockSpec((tm, d),
                                   lambda t, f, te, n: rows_at(t, te, n)),
                      wspec, wspec, wspec],
            out_specs=pl.BlockSpec((tm, d),
                                   lambda t, f, te, n: rows_at(t, te, n))),
        out_shape=jax.ShapeDtypeStruct((n_max * tm, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moe_experts",
    )(tile_expert, jnp.reshape(used, (1,)).astype(jnp.int32), xs,
      w_gate, w_up, w_down)
    # elementwise, so float32 throughout (a contraction would run at the
    # backend's default matmul precision)
    y = jnp.sum(weights.astype(jnp.float32)[..., None]
                * ys[rows].reshape(tokens, k, d), axis=1)
    return y, counts
