"""The paper's five convolution primitives, as composable JAX layers.

Float reference semantics (NHWC, square kernels, SAME padding by default),
matching §2.2 of Nguyen et al. 2023:

  * standard   : dense 2-D convolution (Eq. 1)
  * grouped    : G filter groups (Ioannou et al.)
  * dws        : depthwise-separable = depthwise + pointwise (Szegedy et al.)
  * shift      : per-channel spatial shift + pointwise (Jeon & Kim)
  * add        : L1-distance "AdderNet" convolution (Chen et al., Eq. 3)

Every primitive exposes ``init(key, spec)`` / ``apply(params, x)`` with a
common :class:`ConvSpec`, so models select a primitive by name (the way the
paper swaps NNoM layer implementations).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

Primitives = ("standard", "grouped", "dws", "shift", "add")

# NHWC activations, HWIO weights.
_DN = ("NHWC", "HWIO", "NHWC")


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Structural description of one convolution layer (paper Table 2 axes)."""

    primitive: str = "standard"
    in_channels: int = 16
    out_channels: int = 16
    kernel_size: int = 3
    groups: int = 1           # grouped only
    stride: int = 1
    padding: str = "SAME"
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.primitive not in Primitives:
            raise ValueError(f"unknown primitive {self.primitive!r}")
        if self.primitive == "grouped":
            if self.in_channels % self.groups or self.out_channels % self.groups:
                raise ValueError("groups must divide both channel counts")
        if self.primitive in ("dws", "shift") and self.padding != "SAME":
            raise ValueError(f"{self.primitive} requires SAME padding")

    # ---- paper Table 1: analytic parameter / MAC counts -----------------
    def param_count(self) -> int:
        hk2 = self.kernel_size ** 2
        cx, cy = self.in_channels, self.out_channels
        if self.primitive == "standard":
            return hk2 * cx * cy
        if self.primitive == "grouped":
            return hk2 * (cx // self.groups) * cy
        if self.primitive == "dws":
            return cx * (hk2 + cy)
        if self.primitive == "shift":
            return cx * (2 + cy)   # 2 shift ints per channel + pointwise
        if self.primitive == "add":
            return hk2 * cx * cy
        raise AssertionError

    def mac_count(self, out_width: int) -> int:
        hy2 = out_width ** 2
        hk2 = self.kernel_size ** 2
        cx, cy = self.in_channels, self.out_channels
        if self.primitive == "standard":
            return hk2 * cx * hy2 * cy
        if self.primitive == "grouped":
            return hk2 * (cx // self.groups) * hy2 * cy
        if self.primitive == "dws":
            return cx * hy2 * (hk2 + cy)
        if self.primitive == "shift":
            return cx * cy * hy2
        if self.primitive == "add":
            return hk2 * cx * hy2 * cy
        raise AssertionError


# --------------------------------------------------------------------------
# Parameter initialisation
# --------------------------------------------------------------------------

def init(key: jax.Array, spec: ConvSpec) -> dict:
    """He-normal weights for the given primitive."""
    hk, cx, cy = spec.kernel_size, spec.in_channels, spec.out_channels
    dt = spec.dtype
    ks = jax.random.split(key, 4)

    def he(k, shape, fan_in):
        return (jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5).astype(dt)

    params: dict = {}
    if spec.primitive == "standard":
        params["w"] = he(ks[0], (hk, hk, cx, cy), hk * hk * cx)
    elif spec.primitive == "grouped":
        params["w"] = he(ks[0], (hk, hk, cx // spec.groups, cy), hk * hk * cx // spec.groups)
    elif spec.primitive == "dws":
        params["w_dw"] = he(ks[0], (hk, hk, cx, 1), hk * hk)
        params["w_pw"] = he(ks[1], (1, 1, cx, cy), cx)
    elif spec.primitive == "shift":
        # Jeon & Kim: shifts are assigned, not learned: distribute channels
        # uniformly over the Hk×Hk displacement grid.
        disp = hk // 2
        grid = [(a, b) for a in range(-disp, disp + 1) for b in range(-disp, disp + 1)]
        shifts = jnp.array([grid[i % len(grid)] for i in range(cx)], jnp.int32)
        params["shifts"] = shifts
        params["w_pw"] = he(ks[1], (1, 1, cx, cy), cx)
    elif spec.primitive == "add":
        params["w"] = he(ks[0], (hk, hk, cx, cy), hk * hk * cx)
    if spec.use_bias:
        params["b"] = jnp.zeros((cy,), dt)
    return params


# --------------------------------------------------------------------------
# Forward passes
# --------------------------------------------------------------------------

def _maybe_bias(y, params):
    b = params.get("b")
    return y if b is None else y + b.astype(y.dtype)


def standard_conv(x, w, *, stride=1, padding="SAME", groups=1, preferred=None):
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=_DN, feature_group_count=groups,
        preferred_element_type=preferred,
    )


def depthwise_conv(x, w_dw, *, stride=1, padding="SAME", preferred=None):
    cx = x.shape[-1]
    # HWIO depthwise: (hk, hk, cx, 1) -> feature_group_count = cx needs
    # kernel shaped (hk, hk, 1, cx).
    w = jnp.transpose(w_dw, (0, 1, 3, 2))
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=_DN, feature_group_count=cx,
        preferred_element_type=preferred,
    )


def int8_conv(x, w, *, stride=1, padding="SAME", groups=1):
    """Exact int8 x int8 -> int32 convolution (operands cast to int8 codes),
    the same on every backend. A grouped convolution runs as one dense
    convolution per group: on a TPU v5e, XLA miscomputes integer
    convolutions with ``feature_group_count > 1`` (int8 operands) and
    rounds int32-operand ones once accumulators grow."""
    x, w = x.astype(jnp.int8), w.astype(jnp.int8)
    if groups == 1:
        return standard_conv(x, w, stride=stride, padding=padding,
                             preferred=jnp.int32)
    cxg, cog = x.shape[-1] // groups, w.shape[-1] // groups
    return jnp.concatenate(
        [standard_conv(x[..., g * cxg:(g + 1) * cxg],
                       w[..., g * cog:(g + 1) * cog], stride=stride,
                       padding=padding, preferred=jnp.int32)
         for g in range(groups)], axis=-1)


def shift_channels(x, shifts, *, max_shift: Optional[int] = None):
    """Per-channel spatial shift (Eq. 2): I[k,l,m] = X[k+a_m, l+b_m, m].

    Zero padding at the borders, matching the paper's SAME-padded reading.
    Implemented as a gather on a padded tensor so it vmaps/shards cleanly.

    The padding bound must be a Python int. With a concrete shift table it
    is read off the table; under tracing (jit) callers must pass
    ``max_shift`` (``spec.kernel_size // 2`` for the paper's assignment) —
    a silent fixed bound would corrupt results for larger displacements.
    """
    b, h, w, c = x.shape
    try:                      # concrete shift table: tight padding bound
        pad = max(1, int(jnp.max(jnp.abs(shifts))) if shifts.size else 1)
    except (jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        if max_shift is None:
            raise ValueError(
                "shift_channels: the shift table is traced, so the padding "
                "bound cannot be derived from its values; pass "
                "max_shift=spec.kernel_size // 2 (the maximum |shift| the "
                "table can contain).")
        pad = max(1, int(max_shift))
    if max_shift is not None and pad > max(1, int(max_shift)):
        raise ValueError(
            f"shift_channels: shift table contains |shift|={pad} exceeding "
            f"the declared max_shift={int(max_shift)}")
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    rows = jnp.arange(h)[:, None, None] + pad + shifts[None, None, :, 0]
    cols = jnp.arange(w)[None, :, None] + pad + shifts[None, None, :, 1]
    chan = jnp.arange(c)[None, None, :]
    return xp[:, rows, cols, chan]


def add_conv(x, w, *, padding="SAME"):
    """AdderNet convolution (Eq. 3): Y = -Σ |W - patch|, via patch extraction."""
    hk = w.shape[0]
    cx, cy = w.shape[2], w.shape[3]
    pads = ((hk // 2, (hk - 1) // 2), (hk // 2, (hk - 1) // 2)) if padding == "SAME" else ((0, 0), (0, 0))
    patches = lax.conv_general_dilated_patches(
        x, (hk, hk), (1, 1), pads, dimension_numbers=_DN,
    )  # (B, Hy, Wy, Cx*Hk*Hk) — feature dim ordered (C, kh, kw)
    bsz, hy, wy, _ = patches.shape
    patches = patches.reshape(bsz, hy, wy, cx, hk * hk)
    wk = jnp.transpose(w, (2, 0, 1, 3)).reshape(cx, hk * hk, cy)
    # -Σ_{c,k} |patch[..., c, k] - w[c, k, n]|
    diff = jnp.abs(patches[..., None] - wk[None, None, None])
    return -jnp.sum(diff, axis=(3, 4))


def apply(params: dict, x: jax.Array, spec: ConvSpec) -> jax.Array:
    """Run one primitive layer forward (float path)."""
    p = spec.primitive
    if p == "standard":
        y = standard_conv(x, params["w"], stride=spec.stride, padding=spec.padding)
    elif p == "grouped":
        y = standard_conv(x, params["w"], stride=spec.stride, padding=spec.padding,
                          groups=spec.groups)
    elif p == "dws":
        h = depthwise_conv(x, params["w_dw"], stride=spec.stride, padding=spec.padding)
        y = standard_conv(h, params["w_pw"], stride=1, padding="SAME")
    elif p == "shift":
        h = shift_channels(x, params["shifts"], max_shift=spec.kernel_size // 2)
        y = standard_conv(h, params["w_pw"], stride=spec.stride, padding="SAME")
    elif p == "add":
        y = add_conv(x, params["w"], padding=spec.padding)
    else:
        raise ValueError(p)
    return _maybe_bias(y, params)


# --------------------------------------------------------------------------
# Conv + BatchNorm block (paper couples every primitive with BN; add-conv
# REQUIRES BN to recover positive activations, §2.2)
# --------------------------------------------------------------------------

def init_block(key, spec: ConvSpec, with_bn: bool = True) -> dict:
    kc, _ = jax.random.split(key)
    params = {"conv": init(kc, spec)}
    if with_bn:
        cy = spec.out_channels
        params["bn"] = {
            "gamma": jnp.ones((cy,), spec.dtype),
            "beta": jnp.zeros((cy,), spec.dtype),
            "mean": jnp.zeros((cy,), jnp.float32),
            "var": jnp.ones((cy,), jnp.float32),
        }
    return params


def batchnorm_apply(bn: dict, y: jax.Array, eps: float = 1e-5) -> jax.Array:
    inv = lax.rsqrt(bn["var"] + eps).astype(y.dtype)
    return (y - bn["mean"].astype(y.dtype)) * inv * bn["gamma"].astype(y.dtype) + bn["beta"].astype(y.dtype)


def apply_block(params: dict, x: jax.Array, spec: ConvSpec, *, train_stats=None,
                act=jax.nn.relu) -> jax.Array:
    y = apply(params["conv"], x, spec)
    if "bn" in params:
        if train_stats is not None:
            # batch statistics (training); caller owns the EMA update
            mean = jnp.mean(y, axis=(0, 1, 2))
            var = jnp.var(y, axis=(0, 1, 2))
            train_stats["mean"], train_stats["var"] = mean, var
            bn = dict(params["bn"], mean=mean, var=var)
        else:
            bn = params["bn"]
        y = batchnorm_apply(bn, y)
    return act(y) if act is not None else y
