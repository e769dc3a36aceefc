"""Per-kernel validation: interpret=True Pallas vs pure-jnp oracle (ref.py),
sweeping shapes and dtypes as required for each kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.conv_add import add_conv2d
from repro.kernels.conv_dw import depthwise2d
from repro.kernels.conv_im2col import conv2d_im2col
from repro.kernels.conv_shift import shift_conv2d
from repro.kernels.conv1d_causal import causal_conv1d
from repro.kernels.matmul_q8 import matmul

KEY = jax.random.PRNGKey(0)


def rnd(shape, dtype=jnp.float32, key=KEY, scale=1.0):
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, -100, 100, jnp.int32).astype(dtype)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ conv_im2col --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (N, H, W, Cx, Cy, HK, groups)
    (1, 8, 8, 4, 8, 3, 1),
    (2, 12, 12, 16, 16, 5, 1),
    (1, 9, 9, 6, 9, 3, 3),
    (2, 16, 16, 8, 12, 1, 2),
    (1, 7, 5, 3, 4, 3, 1),      # non-square, odd dims
])
def test_conv_im2col(shape, dtype):
    n, h, w, cx, cy, hk, g = shape
    x = rnd((n, h, w, cx), dtype)
    wt = rnd((hk, hk, cx // g, cy), dtype, jax.random.PRNGKey(1))
    got = conv2d_im2col(x, wt, groups=g, block_co=4)
    want = ref.conv2d_ref(x, wt, groups=g)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("shift", [0, 3, 7, -1])
def test_conv_im2col_int8(shift):
    x = rnd((2, 8, 8, 8), jnp.int8)
    w = rnd((3, 3, 8, 16), jnp.int8, jax.random.PRNGKey(2))
    got = conv2d_im2col(x, w, requant_shift=shift)
    want = ref.conv2d_q8_ref(x, w, requant_shift=shift)
    np.testing.assert_array_equal(got, want)        # integer path: bit exact


def test_conv_im2col_int8_bias():
    x = rnd((1, 6, 6, 4), jnp.int8)
    w = rnd((3, 3, 4, 8), jnp.int8, jax.random.PRNGKey(3))
    b = jnp.arange(8, dtype=jnp.int32) * 50
    got = conv2d_im2col(x, w, bias=b, requant_shift=5)
    want = ref.conv2d_q8_ref(x, w, b, requant_shift=5)
    np.testing.assert_array_equal(got, want)


# -------------------------------------------------------------- conv_dw ---
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,hk", [(1, 8, 8, 8, 3), (2, 10, 6, 16, 5), (1, 5, 5, 3, 1)])
def test_depthwise(n, h, w, c, hk, dtype):
    x = rnd((n, h, w, c), dtype)
    wd = rnd((hk, hk, c), dtype, jax.random.PRNGKey(1))
    got = depthwise2d(x, wd, block_c=4)
    want = ref.depthwise2d_ref(x, wd)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))


def test_depthwise_int8():
    x = rnd((1, 6, 6, 8), jnp.int8)
    wd = rnd((3, 3, 8), jnp.int8, jax.random.PRNGKey(1))
    got = depthwise2d(x, wd, requant_shift=4)
    want = ref.depthwise2d_q8_ref(x, wd, requant_shift=4)
    np.testing.assert_array_equal(got, want)
    # golden: round-to-nearest epilogue (NNoM default build), not floor
    acc = ref.depthwise2d_ref(x.astype(jnp.int32), wd.astype(jnp.int32))
    rounded = jnp.clip(jnp.right_shift(acc + 8, 4), -128, 127).astype(jnp.int8)
    np.testing.assert_array_equal(got, rounded)


# ------------------------------------------------------------ conv_shift --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("c,cy,h", [(4, 8, 8), (9, 6, 10), (16, 16, 12)])
def test_shift_conv(c, cy, h, dtype):
    x = rnd((2, h, h, c), dtype)
    grid = [(a, b) for a in (-1, 0, 1) for b in (-1, 0, 1)]
    shifts = np.array([grid[i % 9] for i in range(c)], np.int32)
    w = rnd((c, cy), dtype, jax.random.PRNGKey(1))
    got = shift_conv2d(x, shifts, w, block_co=4)
    want = ref.shift_conv2d_ref(x, shifts, w)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))


def test_shift_conv_int8():
    c, cy = 6, 8
    x = rnd((1, 8, 8, c), jnp.int8)
    shifts = np.array([[(i % 3) - 1, ((i * 2) % 3) - 1] for i in range(c)], np.int32)
    w = rnd((c, cy), jnp.int8, jax.random.PRNGKey(1))
    got = shift_conv2d(x, shifts, w, requant_shift=5)
    want = ref.shift_conv2d_q8_ref(x, shifts, w, requant_shift=5)
    np.testing.assert_array_equal(got, want)
    from repro.core.primitives import shift_channels, standard_conv
    acc = standard_conv(shift_channels(x.astype(jnp.int32), jnp.asarray(shifts)),
                        w[None, None].astype(jnp.int32))
    # golden: + (1 << (shift-1)) rounding term before the arithmetic shift
    rounded = jnp.clip(jnp.right_shift(acc + 16, 5), -128, 127).astype(jnp.int8)
    np.testing.assert_array_equal(got, rounded)


# -------------------------------------------------------------- conv_add --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cx,cy,hk", [(4, 8, 3), (3, 5, 5), (8, 4, 1)])
def test_add_conv(cx, cy, hk, dtype):
    x = rnd((2, 7, 7, cx), dtype)
    w = rnd((hk, hk, cx, cy), dtype, jax.random.PRNGKey(1))
    got = add_conv2d(x, w, block_co=2)
    want = ref.add_conv2d_ref(x, w)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))


def test_add_conv_int8_algorithm1():
    """int path incl. the Algorithm-1 (right) scale alignment pre-shift."""
    x = rnd((1, 6, 6, 4), jnp.int8)
    w = rnd((3, 3, 4, 6), jnp.int8, jax.random.PRNGKey(1))
    # fb_x=5, fb_w=3 -> align w by <<2, acc fb=5, out fb=2 -> shift 3
    got = add_conv2d(x, w, requant_shift=3, w_preshift=2)
    from repro.core.primitives import add_conv
    acc = add_conv(x.astype(jnp.float32), (w.astype(jnp.float32) * 4.0))
    want = jnp.clip(jnp.floor(acc / 8.0), -128, 127).astype(jnp.int8)
    np.testing.assert_allclose(got, want, atol=1)   # float ref rounding slack


# --------------------------------------------------------- conv1d_causal --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,l,d,k,bl", [
    (2, 16, 8, 4, 8), (1, 64, 16, 4, 16), (3, 10, 4, 2, 5), (1, 8, 4, 4, 8),
])
def test_causal_conv1d(b, l, d, k, bl, dtype):
    x = rnd((b, l, d), dtype)
    w = rnd((k, d), dtype, jax.random.PRNGKey(1))
    got = causal_conv1d(x, w, block_l=bl, block_c=4)
    want = ref.causal_conv1d_ref(x, w)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype))


def test_causal_conv1d_is_causal():
    """Changing x[t0:] must not change outputs before t0."""
    x = rnd((1, 32, 4))
    w = rnd((4, 4), key=jax.random.PRNGKey(1))
    y1 = causal_conv1d(x, w, block_l=8, block_c=4)
    x2 = x.at[:, 20:, :].set(99.0)
    y2 = causal_conv1d(x2, w, block_l=8, block_c=4)
    np.testing.assert_allclose(y1[:, :20], y2[:, :20], rtol=1e-6)


# ------------------------------------------------------------- matmul_q8 --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (32, 64, 16, 16, 8, 32), (128, 128, 128, 64, 64, 64), (8, 16, 8, 8, 8, 8),
])
def test_matmul(m, k, n, bm, bn, bk, dtype):
    a = rnd((m, k), dtype, scale=0.3)
    b = rnd((k, n), dtype, jax.random.PRNGKey(1), scale=0.3)
    got = matmul(a, b, bm=bm, bn=bn, bk=bk)
    want = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-1 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("shift", [0, 4, 8])
def test_matmul_int8(shift):
    a = rnd((64, 96), jnp.int8)
    b = rnd((96, 32), jnp.int8, jax.random.PRNGKey(1))
    got = matmul(a, b, bm=32, bn=16, bk=32, requant_shift=shift)
    want = ref.matmul_ref(a, b, requant_shift=shift)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------- shift_channels padding bound --
def test_shift_channels_large_shift_jit():
    """Regression: the traced-shift fallback used a hard-coded pad=8, which
    silently corrupted results for |shift| > 8 (kernel_size > 17). With the
    bound passed explicitly the jitted gather must match the concrete one."""
    from repro.core.primitives import shift_channels
    c, s = 4, 9                                  # |shift|=9 broke pad=8
    x = rnd((1, 24, 24, c))
    shifts = jnp.array([[s, -s], [-s, s], [s, s], [0, -s]], jnp.int32)
    want = shift_channels(x, shifts)             # concrete: tight bound
    got = jax.jit(lambda xx, ss: shift_channels(xx, ss, max_shift=s))(x, shifts)
    np.testing.assert_array_equal(got, want)


def test_shift_channels_traced_without_bound_raises():
    from repro.core.primitives import shift_channels
    x = rnd((1, 8, 8, 2))
    shifts = jnp.array([[1, 0], [0, 1]], jnp.int32)
    with pytest.raises(ValueError, match="max_shift"):
        jax.jit(shift_channels)(x, shifts)


def test_shift_channels_bound_violation_raises():
    from repro.core.primitives import shift_channels
    x = rnd((1, 8, 8, 2))
    shifts = jnp.array([[5, 0], [0, -5]], jnp.int32)
    with pytest.raises(ValueError, match="exceeding"):
        shift_channels(x, shifts, max_shift=2)


# ----------------------------------------------- ops method= validation ---
def test_ops_xla_with_explicit_config_rejected():
    """Satellite: method='xla' used to silently ignore an explicit config=
    (and matmul its bm/bn/bk); now it raises the conflicting-arguments
    error, mirroring _check_method."""
    from repro.kernels import ops
    x = rnd((1, 8, 8, 4))
    w = rnd((3, 3, 4, 8), key=jax.random.PRNGKey(1))
    for fn, args in [
        (ops.conv2d, (x, w)),
        (ops.depthwise2d, (x, rnd((3, 3, 4)))),
        (ops.add_conv2d, (x, w)),
        (ops.shift_conv2d, (x, jnp.zeros((4, 2), jnp.int32), rnd((4, 8)))),
        (ops.causal_conv1d, (rnd((1, 16, 4)), rnd((4, 4)))),
        (ops.matmul, (rnd((8, 8)), rnd((8, 8)))),
        (ops.maxpool2d, (rnd((1, 8, 8, 4)),)),
    ]:
        with pytest.raises(ValueError, match="config"):
            fn(*args, method="xla", config={"block_co": 8})
    with pytest.raises(ValueError, match="config"):
        ops.matmul(rnd((8, 8)), rnd((8, 8)), method="xla", bm=8)
    # pallas keeps accepting explicit schedules
    got = ops.matmul(rnd((8, 8)), rnd((8, 8), key=jax.random.PRNGKey(2)),
                     method="pallas", bm=8, bn=8, bk=8)
    assert got.shape == (8, 8)


def test_ops_maxpool2d_shapes_and_parity():
    x = rnd((2, 9, 9, 4))
    got = ops_maxpool_both(x, window=3, stride=3)
    assert got[0].shape == (2, 3, 3, 4)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1]))


def ops_maxpool_both(x, **kw):
    from repro.kernels import ops
    return ops.maxpool2d(x, method="pallas", **kw), \
        ops.maxpool2d(x, method="xla", **kw)


def test_ops_unknown_method_rejected():
    from repro.kernels import ops
    x = rnd((1, 8, 8, 4))
    w = rnd((3, 3, 4, 8), key=jax.random.PRNGKey(1))
    for fn, args in [
        (ops.conv2d, (x, w)),
        (ops.depthwise2d, (x, rnd((3, 3, 4)))),
        (ops.add_conv2d, (x, w)),
        (ops.shift_conv2d, (x, jnp.zeros((4, 2), jnp.int32), rnd((4, 8)))),
        (ops.causal_conv1d, (rnd((1, 16, 4)), rnd((4, 4)))),
        (ops.matmul, (rnd((8, 8)), rnd((8, 8)))),
    ]:
        with pytest.raises(ValueError, match="unknown method"):
            fn(*args, method="nope")


_I8 = jnp.zeros((2, 8, 8, 16), jnp.int8)


@pytest.mark.parametrize("oracle,args,kw,n_contractions", [
    (ref.conv2d_q8_ref, (_I8, jnp.zeros((3, 3, 8, 32), jnp.int8)),
     {"groups": 2, "requant_shift": 4}, 2),
    (ref.depthwise2d_q8_ref, (_I8, jnp.zeros((3, 3, 16), jnp.int8)),
     {"requant_shift": 4}, 0),
    (ref.shift_conv2d_q8_ref,
     (_I8, np.zeros((16, 2), np.int32), jnp.zeros((16, 32), jnp.int8)),
     {"requant_shift": 4, "max_shift": 1}, 1),
    (ref.matmul_ref, (jnp.zeros((8, 64), jnp.int8),
                      jnp.zeros((64, 32), jnp.int8)), {"requant_shift": 4}, 1),
], ids=["conv2d", "depthwise2d", "shift_conv2d", "matmul"])
def test_integer_oracles_contract_int8_operands(oracle, args, kw,
                                                n_contractions):
    """The integer oracles feed int8 codes to every contraction, accumulate
    in int32, and never use a grouped convolution: on a v5e, XLA rounds
    int32 x int32 convolutions once accumulators grow and miscomputes
    grouped int8 ones, which made the oracle (not the kernel) disagree."""
    jaxpr = jax.make_jaxpr(lambda *a: oracle(*a, **kw))(*args)
    eqns = [e for e in jaxpr.jaxpr.eqns
            if e.primitive.name in ("conv_general_dilated", "dot_general")]
    assert len(eqns) == n_contractions
    for e in eqns:
        assert [v.aval.dtype for v in e.invars] == [jnp.int8, jnp.int8]
        assert e.outvars[0].aval.dtype == jnp.int32
        assert e.params.get("feature_group_count", 1) == 1
