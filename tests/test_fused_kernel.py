"""Fused quantize->matmul kernel and the mx_dot Pallas backend vs the jnp
reference (interpret mode).

Forward parity is BITWISE whenever K fits one kernel tile (the kernel then
performs the same single f32 contraction as the reference); multi-K-tile
accumulation and gradients are checked to f32 accumulation tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocking as B
from repro.core.mx_dot import count_quant_passes, mx_dot
from repro.core.policy import QuantPolicy
from repro.kernels import ops, ref

LAYOUTS = [((1, 32), (32, 1)), ((8, 8), (8, 8))]
slow = pytest.mark.slow


def _rand(shape, scale_sigma=2.0, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape) * np.exp(
        rng.standard_normal(shape) * scale_sigma)
    return jnp.asarray(x.astype(np.float32))


def _edge_rows(cols=64):
    """Zeros, f32 denormals, giant finite blocks — inf-free edge inputs."""
    rows = [
        np.zeros(cols, np.float32),
        np.full(cols, 1e-40, np.float32),                       # subnormal
        (np.linspace(1, cols, cols) * 1e-42).astype(np.float32),
        np.full(cols, 3.0e38, np.float32),                      # S_e = 127
        np.where(np.arange(cols) % 2, 2.0 ** -130, 1.0).astype(np.float32),
        np.where(np.arange(cols) % 3, -(2.0 ** -149),
                 3.4e38).astype(np.float32),
        (np.random.default_rng(0).standard_normal(cols)
         * 1e38).astype(np.float32),
        np.full(cols, 2.0 ** -126, np.float32),
    ]
    return jnp.asarray(np.stack(rows))


# ---------------------------------------------------------------------------
# fused kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("xblk,wblk", LAYOUTS)
@pytest.mark.parametrize("mkn", [(32, 128, 64),
                                 pytest.param((64, 256, 48), marks=slow),
                                 pytest.param((8, 64, 128), marks=slow)])
def test_fused_matmul_bitexact(xblk, wblk, mkn):
    m, k, n = mkn
    x, w = _rand((m, k), seed=1), _rand((k, n), seed=2)
    wc, ws = ops.mxsf_quantize(w, block=wblk)
    y = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk)
    yr = ref.mxsf_fused_matmul_ref(x, wc, ws, xblk, wblk)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr)[:m, :n])


@pytest.mark.parametrize("xblk,wblk", LAYOUTS)
@pytest.mark.parametrize("mkn", [pytest.param((30, 100, 24), marks=slow),
                                 (17, 70, 33)])
def test_fused_matmul_non_tile_aligned(xblk, wblk, mkn):
    """Padding/crop path: shapes that divide neither tiles nor blocks."""
    m, k, n = mkn
    x, w = _rand((m, k), seed=3), _rand((k, n), seed=4)
    wc, ws = ops.mxsf_quantize(w, block=wblk)
    # the wrapper's N is w_codes' block-padded N; crop to the true N here
    y = np.asarray(ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk))
    yr = np.asarray(ref.mxsf_fused_matmul_ref(x, wc, ws, xblk, wblk))
    np.testing.assert_array_equal(y[:, :n], yr[:m, :n])
    assert (y[:, n:] == 0).all()  # padded-weight columns contribute zeros


def test_fused_matmul_edge_inputs():
    x = _edge_rows(64)
    w = _rand((64, 48), seed=5)
    for xblk, wblk in LAYOUTS:
        wc, ws = ops.mxsf_quantize(w, block=wblk)
        y = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk)
        yr = ref.mxsf_fused_matmul_ref(x, wc, ws, xblk, wblk)
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(yr)[: x.shape[0]])


def test_fused_matmul_emit_codes_match_reference_quantizer():
    x = _rand((64, 128), seed=6)
    w = _rand((128, 32), seed=7)
    for xblk, wblk in LAYOUTS:
        wc, ws = ops.mxsf_quantize(w, block=wblk)
        y, xc, xs = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk,
                                          emit_codes=True)
        qt = B.quantize(x, "mxsf", xblk)
        np.testing.assert_array_equal(np.asarray(xc), np.asarray(qt.codes))
        np.testing.assert_array_equal(np.asarray(xs),
                                      np.asarray(qt.scale_e8m0))
        # emitting codes must not perturb the matmul
        y0 = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


def test_fused_matmul_quantize_lhs_false():
    """Raw-LHS mode (the quantize_bwd=False gradient path)."""
    x, w = _rand((32, 64), seed=8), _rand((64, 32), seed=9)
    wc, ws = ops.mxsf_quantize(w, block=(32, 1))
    y = ops.mxsf_fused_matmul(x, wc, ws, (1, 32), (32, 1),
                              quantize_lhs=False)
    yr = ref.mxsf_fused_matmul_ref(x, wc, ws, (1, 32), (32, 1),
                                   quantize_lhs=False)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_fused_matmul_multi_k_tile_accumulation():
    """K split over several kernel tiles: f32 accumulation tolerance."""
    x, w = _rand((32, 512), seed=10), _rand((512, 32), seed=11)
    wc, ws = ops.mxsf_quantize(w, block=(32, 1))
    y = ops.mxsf_fused_matmul(x, wc, ws, (1, 32), (32, 1), tk=128)
    yr = ref.mxsf_fused_matmul_ref(x, wc, ws, (1, 32), (32, 1))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=np.abs(np.asarray(yr)).max() * 1e-6)


def test_fused_matmul_bf16_input():
    x = _rand((32, 64), seed=12).astype(jnp.bfloat16)
    w = _rand((64, 32), seed=13)
    wc, ws = ops.mxsf_quantize(w, block=(32, 1))
    y = ops.mxsf_fused_matmul(x, wc, ws, (1, 32), (32, 1))
    yr = ref.mxsf_fused_matmul_ref(x.astype(jnp.float32), wc, ws,
                                   (1, 32), (32, 1))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


# ---------------------------------------------------------------------------
# grids with several tiles along M, N and K: the decoded row block is
# converted at j == 0 into its kk slot and read back for every later j
# ---------------------------------------------------------------------------

# ((m, k, n), (tm, tn, tk)): tile-aligned, grid (2, 3, 2); padded on every
# dim, grid (3, 3, 3) under both layouts
GRIDS = [((32, 128, 96), (16, 32, 64)), ((17, 70, 33), (8, 16, 32))]


def _exact(shape, axis, seed):
    """Integers in [-16, 16] scaled by 2^e, one e per row (``axis=1``) or
    column (``axis=0``): MXSF and bf16 hold them exactly, and every
    product and partial sum of x @ w is exact in f32, so the result is
    that of one contraction bit for bit in any K order."""
    rng = np.random.default_rng(seed)
    ints = rng.integers(-16, 17, shape).astype(np.float32)
    e = rng.integers(-20, 21, shape[1 - axis])
    return jnp.asarray(ints * np.expand_dims(2.0 ** e, axis)
                       .astype(np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mkn,tiles", GRIDS, ids=["aligned", "padded"])
@pytest.mark.parametrize("xblk,wblk", LAYOUTS, ids=["1d", "2d"])
def test_fused_matmul_multi_tile_grid(xblk, wblk, mkn, tiles, dtype):
    m, k, n = mkn
    tm, tn, tk = tiles
    x = _exact((m, k), 1, seed=40).astype(dtype)
    w = _exact((k, n), 0, seed=41)
    wc, ws = ops.mxsf_quantize(w, block=wblk)
    kw = dict(tm=tm, tn=tn, tk=tk)
    y = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk, **kw)
    yr = ref.mxsf_fused_matmul_ref(x.astype(jnp.float32), wc, ws, xblk, wblk)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr)[:m])
    y2, xc, xs = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk,
                                       emit_codes=True, **kw)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))
    qt = B.quantize(x.astype(jnp.float32), "mxsf", xblk)
    np.testing.assert_array_equal(np.asarray(xc), np.asarray(qt.codes))
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(qt.scale_e8m0))


@pytest.mark.parametrize("xblk,wblk", LAYOUTS, ids=["1d", "2d"])
def test_fused_matmul_multi_tile_edge_inputs(xblk, wblk):
    """The edge rows (zeros, subnormals, S_e = 127) among random ones on a
    (3, 3, 1) grid: every output tile past the first reads the decoded
    row block back.  Each tile is held bitwise to the reference on that
    tile's rows and columns (one contraction of the same shape, so the
    overflowing partial sums of the edge rows round alike)."""
    x = jnp.concatenate([_edge_rows(64), _rand((16, 64), seed=42)])
    w = _rand((64, 48), seed=43)
    wc, ws = ops.mxsf_quantize(w, block=wblk)
    y, xc, xs = ops.mxsf_fused_matmul(x, wc, ws, xblk, wblk, tm=8, tn=16,
                                      tk=64, emit_codes=True)
    for i in range(0, 24, 8):
        for j in range(0, 48, 16):
            yr = ref.mxsf_fused_matmul_ref(
                x[i:i + 8], wc[:, j:j + 16],
                ws[:, j // wblk[1]:(j + 16) // wblk[1]], xblk, wblk)
            np.testing.assert_array_equal(np.asarray(y[i:i + 8, j:j + 16]),
                                          np.asarray(yr))
    qt = B.quantize(x, "mxsf", xblk)
    np.testing.assert_array_equal(np.asarray(xc), np.asarray(qt.codes))
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(qt.scale_e8m0))


def test_fused_matmul_convert_count():
    """A grid of (2, 3, 2) tiles converts (M/TM) * (K/TK) = 4 activation
    tiles in its 12 grid steps, with or without emitted codes; one output
    tile along N converts in every step, and quantize_lhs=False in none."""
    x, w = _rand((32, 128), seed=44), _rand((128, 96), seed=45)
    wc, ws = ops.mxsf_quantize(w, block=(32, 1))

    def count(tn=32, **kw):
        c0 = ops.fused_lhs_converts()
        ops.mxsf_fused_matmul(x, wc, ws, (1, 32), (32, 1), tm=16, tn=tn,
                              tk=64, **kw)
        return tuple(b - a for a, b in zip(c0, ops.fused_lhs_converts()))

    assert count() == (4, 12)
    assert count(emit_codes=True) == (4, 12)
    assert count(quantize_lhs=False) == (0, 12)
    assert count(tn=96) == (4, 4)


def test_kernel_opcount_converter_scales_with_rows():
    """benchmarks/kernel_opcount.py: the converter's element-ops grow in
    step with the activation tile's rows, the weight decode's not at all."""
    from benchmarks.kernel_opcount import count

    (a8, w8), (a16, w16), (a24, w24) = (count(tm, 128, 128)
                                        for tm in (8, 16, 24))
    assert a24 - a16 == a16 - a8 > 0
    assert w8 == w16 == w24 > 0


def test_fused_matmul_steps_tiny(capsys):
    """benchmarks/fused_matmul_steps.py --tiny: one line per shape with the
    grid's steps and the converter's count (one K tile, four N tiles: one
    conversion in four steps)."""
    from benchmarks.fused_matmul_steps import main

    main(["--tiny"])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("KB|")]
    assert len(lines) == 2, lines
    for ln in lines:
        assert "|steps=4|counted=1/4|sums=" in ln, ln


# ---------------------------------------------------------------------------
# mx_dot backend="pallas" vs backend="jnp"
# ---------------------------------------------------------------------------

P2D = QuantPolicy(block_mode="2d", tile=8)
P1D = QuantPolicy(block_mode="1d", block_1d=32)


def _loss(pol):
    return lambda x, w: (mx_dot(x, w, pol) ** 2).sum()


@pytest.mark.parametrize("pol", [P2D, P1D], ids=["2d", "1d"])
def test_mx_dot_pallas_forward_bitwise(pol):
    x, w = _rand((4, 16, 64), seed=20), _rand((64, 32), seed=21)
    yj = mx_dot(x, w, pol)
    yp = mx_dot(x, w, pol.replace(backend="pallas"))
    np.testing.assert_array_equal(np.asarray(yj), np.asarray(yp))


@pytest.mark.parametrize("pol", [pytest.param(P2D, marks=slow), P1D],
                         ids=["2d", "1d"])
def test_mx_dot_pallas_forward_non_aligned_shapes(pol):
    x, w = _rand((3, 10, 50), seed=22), _rand((50, 24), seed=23)
    yj = mx_dot(x, w, pol)
    yp = mx_dot(x, w, pol.replace(backend="pallas"))
    np.testing.assert_array_equal(np.asarray(yj), np.asarray(yp))


@pytest.mark.parametrize("quantize_bwd", [True, False])
@pytest.mark.parametrize("pol", [P2D, P1D], ids=["2d", "1d"])
def test_mx_dot_pallas_grads(pol, quantize_bwd):
    pol = pol.replace(quantize_bwd=quantize_bwd)
    x, w = _rand((4, 16, 64), seed=24), _rand((64, 32), seed=25)
    gj = jax.grad(_loss(pol), argnums=(0, 1))(x, w)
    gp = jax.grad(_loss(pol.replace(backend="pallas")), argnums=(0, 1))(x, w)
    for a, b in zip(gj, gp):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5,
            atol=np.abs(np.asarray(a)).max() * 1e-6)


@pytest.mark.parametrize("pol,expect", [(P1D, 6), (P2D, 3)], ids=["1d", "2d"])
def test_mx_dot_pallas_pass_accounting(pol, expect):
    """Fig. 4 accounting survives the backend swap: 1D=6, 2D=3."""
    x, w = _rand((4, 16, 64), seed=26), _rand((64, 32), seed=27)
    with count_quant_passes() as c:
        jax.grad(_loss(pol.replace(backend="pallas")), argnums=(0, 1))(x, w)
    assert c["n"] == expect


def test_mx_dot_pallas_value_only_path():
    """The primal (no-grad) call must not emit activation codes but still
    match the jnp reference bitwise."""
    x, w = _rand((8, 64), seed=28), _rand((64, 32), seed=29)
    yj = jax.jit(lambda x, w: mx_dot(x, w, P2D))(x, w)
    yp = jax.jit(lambda x, w: mx_dot(x, w,
                                     P2D.replace(backend="pallas")))(x, w)
    np.testing.assert_array_equal(np.asarray(yj), np.asarray(yp))


def test_serve_engine_backend_switch():
    """ServeEngine(backend=...) rewrites the policy and validates eagerly."""
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = QuantPolicy(block_mode="1d", block_1d=32, quantize_bwd=False)
    eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                      backend="pallas")
    assert eng.policy.backend == "pallas" and eng.policy.use_pallas
    with pytest.raises(ValueError, match="MXSF"):
        ServeEngine(cfg, params, pol.replace(fwd_fmt="mxfp8_e4m3"),
                    slots=2, max_len=16, backend="pallas")


@slow
def test_serve_engine_pallas_decode_matches_jnp():
    """Same generated tokens through both backends (forward is bitwise)."""
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = QuantPolicy(block_mode="1d", block_1d=32, quantize_bwd=False)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, size=n)) for n in (3, 2)]
    outs = []
    for backend in (None, "pallas"):
        eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                          backend=backend)
        reqs = [eng.submit(p, 3) for p in prompts]
        eng.run()
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_mx_dot_degenerate_shapes(backend):
    """Zero-sized dims must not crash either backend (fwd and grads)."""
    pol = QuantPolicy(block_mode="1d", block_1d=32, backend=backend)
    for xs, ws in [((0, 32), (32, 8)), ((4, 0), (0, 8)),
                   ((2, 3, 32), (32, 0)), ((2, 0, 32), (32, 8))]:
        x, w = jnp.zeros(xs), jnp.zeros(ws)
        y = mx_dot(x, w, pol)
        assert y.shape == xs[:-1] + (ws[-1],)
        dx, dw = jax.grad(lambda x, w: mx_dot(x, w, pol).sum(),
                          argnums=(0, 1))(x, w)
        assert dx.shape == xs and dw.shape == ws


def test_pallas_backend_rejects_non_mxsf():
    pol = QuantPolicy(fwd_fmt="mxfp8_e4m3", backend="pallas")
    with pytest.raises(ValueError, match="MXSF"):
        _ = pol.use_pallas
    with pytest.raises(ValueError, match="backend"):
        _ = QuantPolicy(backend="cuda").use_pallas
    # disabled policies never dispatch, whatever the backend says
    assert not QuantPolicy(block_mode="none", backend="pallas").use_pallas
