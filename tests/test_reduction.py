"""Reduction algebra: quantization numerics, wire accounting, resolution.

The quantized-allreduce error model under test (ops/reduction.py): with
shared per-block scales ``s = gmax/qmax``, each rank's contribution
quantizes with error <= s/2, the narrow-container sums are EXACT, and the
allgather re-quantization adds one more s'/2 — so an n-rank SUM is off by
at most ``(n + n) * gmax / (2*qmax)`` per element (reduce-scatter n
contributions + requant of an n-scaled result), and an AVERAGE by
``2 * gmax / (2*qmax)``.  Tests assert these bounds with a 1.5x safety
margin (fp32 arithmetic inside the kernel adds ulps, not halves).
"""

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops import reduction as R

N = 8


@pytest.fixture(autouse=True)
def _no_size_floor():
    cfg = hvd.global_state().config
    old_floor, old_block, old_mode = (
        cfg.quant_min_bytes, cfg.quant_block_size, cfg.wire_precision)
    cfg.quant_min_bytes = 0
    yield
    cfg.quant_min_bytes = old_floor
    cfg.quant_block_size = old_block
    cfg.wire_precision = old_mode


# ---------------------------------------------------------------------------
# encode/decode round trip: per-block error bounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [64, 256, 512])
@pytest.mark.parametrize("mode,qmax", [("int8", 127.0), ("fp8", 448.0)])
def test_roundtrip_error_bound_per_block(mode, qmax, block):
    import jax.numpy as jnp
    alg = R.algebra_for(mode)
    rng = np.random.RandomState(7)
    x = (rng.randn(12, block) * 10 ** rng.uniform(-3, 3, (12, 1))
         ).astype(np.float32)
    wire, scales = alg.wire_encode(jnp.asarray(x))
    back = np.asarray(alg.wire_decode(wire, scales))
    amax = np.abs(x).max(axis=-1, keepdims=True)
    if mode == "int8":
        bound = amax / (2 * qmax) * 1.001      # half a quantization step
    else:
        # e4m3: 3 mantissa bits -> rel err <= 2^-4 of the value, but
        # bound per block by the scale-normalized worst case.
        bound = amax * 2.0 ** -4 * 1.001
    assert (np.abs(back - x) <= bound + 1e-12).all(), mode


def test_roundtrip_zero_block_finite():
    import jax.numpy as jnp
    for mode in ("int8", "fp8"):
        alg = R.algebra_for(mode)
        x = jnp.zeros((2, 64), jnp.float32)
        wire, scales = alg.wire_encode(x)
        back = np.asarray(alg.wire_decode(wire, scales))
        assert np.isfinite(back).all() and (back == 0).all()


# ---------------------------------------------------------------------------
# allreduce parity vs fp32, both engine-visible paths
# ---------------------------------------------------------------------------

def _parity_case(mode, op, block=512, numel=5000, seed=0):
    cfg = hvd.global_state().config
    cfg.quant_block_size = block
    rng = np.random.RandomState(seed)
    parts = [rng.randn(numel).astype(np.float32) for _ in range(N)]
    x = hvd.per_rank(parts)
    exact = np.stack(parts).sum(0)
    if op is hvd.Average:
        exact = exact / N
    got = hvd.to_numpy(C.allreduce(x, op, precision=mode))
    gmax = max(np.abs(p).max() for p in parts)
    scale_sum = N if op is hvd.Sum else 1.0
    if mode == "int8":
        atol = 1.5 * (N + scale_sum) * gmax / 254.0
    elif mode == "fp8":
        atol = 1.5 * (N + scale_sum) * gmax / 16.0
    else:  # bf16/fp16 cast wire: 8-bit / 11-bit mantissa sums
        atol = (N + scale_sum) * gmax * (2.0 ** -7)
    np.testing.assert_allclose(got, exact, atol=atol)
    return np.abs(got - exact).max(), atol


@pytest.mark.parametrize("mode", ["bf16", "fp16", "int8", "fp8"])
@pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
def test_allreduce_parity_within_tolerance(mode, op):
    err, atol = _parity_case(mode, op)
    assert err > 0 or mode in ("bf16", "fp16")  # quantization is lossy


@pytest.mark.parametrize("block", [64, 512])
def test_allreduce_parity_across_block_sizes(block):
    _parity_case("int8", hvd.Average, block=block, numel=3000, seed=3)


def test_allreduce_unaligned_sizes_pad_correctly():
    # numel not divisible by n*block exercises the pad/unpad path.
    for numel in (1, 7, 513, 4097):
        _parity_case("int8", hvd.Sum, numel=numel, seed=numel)


def test_grouped_allreduce_quantized_parity():
    rng = np.random.RandomState(1)
    groups = [[rng.randn(130).astype(np.float32) for _ in range(N)]
              for _ in range(4)]
    outs = C.grouped_allreduce(
        [hvd.per_rank(p) for p in groups], hvd.Average, precision="int8")
    for parts, out in zip(groups, outs):
        exact = np.stack(parts).mean(0)
        gmax = np.abs(np.stack(parts)).max()
        np.testing.assert_allclose(hvd.to_numpy(out), exact,
                                   atol=1.5 * (N + 1) * gmax / 254.0)


def test_engine_async_fused_quantized_parity():
    handles, exacts, gmaxes = [], [], []
    rng = np.random.RandomState(2)
    for i in range(6):
        parts = [rng.randn(257).astype(np.float32) for _ in range(N)]
        exacts.append(np.stack(parts).mean(0))
        gmaxes.append(np.abs(np.stack(parts)).max())
        handles.append(hvd.allreduce_async(
            hvd.per_rank(parts), hvd.Average, name=f"t.red.q{i}",
            compression="int8"))
    for h, exact, gmax in zip(handles, exacts, gmaxes):
        got = hvd.to_numpy(hvd.synchronize(h))
        np.testing.assert_allclose(got, exact,
                                   atol=1.5 * (N + 1) * gmax / 254.0)


def test_zero_block_rank_does_not_poison_shared_scale():
    """Regression (review finding): a rank whose block is all zeros
    (frozen layer, sparse gradient, or a joined rank's fabricated zero
    payload) must not drag the mesh-agreed scale to the 1.0 sentinel —
    the pmax runs over RAW absmax, so small real magnitudes on the other
    ranks survive quantization."""
    cfg = hvd.global_state().config
    cfg.quant_block_size = 512
    small = 0.01
    parts = [np.zeros(1024, np.float32)] + \
        [np.full(1024, small, np.float32) for _ in range(N - 1)]
    exact = np.stack(parts).mean(0)
    for mode, qmax in (("int8", 127.0), ("fp8", 448.0)):
        got = hvd.to_numpy(C.allreduce(hvd.per_rank(parts), hvd.Average,
                                       precision=mode))
        # Pre-fix this returned exactly 0 (error == exact); post-fix the
        # error is bounded by the documented shared-scale model.
        atol = 1.5 * (N + 1) * small / (2 * qmax)
        np.testing.assert_allclose(got, exact, atol=atol)
        assert np.abs(got).max() > 0, mode


def test_in_context_zero_block_rank():
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    state = hvd.global_state()
    mesh, axis = state.mesh, state.config.dp_axis_name

    def kern(v):
        return R.in_context_allreduce(v[0], axis, "int8", average=True)[None]

    fn = jax.jit(shard_map(kern, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False))
    parts = np.full((N, 512), 0.02, np.float32)
    parts[0] = 0.0
    out = np.asarray(fn(hvd.per_rank(list(parts))))
    exact = parts.mean(0)
    np.testing.assert_allclose(out[0], exact,
                               atol=1.5 * (N + 1) * 0.02 / 254.0)
    assert np.abs(out).max() > 0


def test_compression_namespace_routes_modes():
    assert R.as_wire_mode(hvd.Compression.int8) == "int8"
    assert R.as_wire_mode(hvd.Compression.fp8) == "fp8"
    assert R.as_wire_mode(hvd.Compression.fp16) == "bf16"
    assert R.as_wire_mode(hvd.Compression.fp16_ieee) == "fp16"
    assert R.as_wire_mode(hvd.Compression.none) == ""
    assert R.as_wire_mode(None) == ""
    with pytest.raises(ValueError):
        R.as_wire_mode("int4")


def test_bf16_fp16_compressor_parity_retained():
    """The legacy host-side Compression path (torch/tf wrappers) must
    keep its semantics alongside the engine wire modes."""
    import jax.numpy as jnp
    from horovod_tpu.ops.compression import Compression
    x = jnp.asarray(np.linspace(-4, 4, 256, dtype=np.float32))
    for comp, wdt in ((Compression.fp16, jnp.bfloat16),
                      (Compression.fp16_ieee, jnp.float16)):
        wire, ctx = comp.compress(x)
        assert wire.dtype == wdt
        back = comp.decompress(wire, ctx)
        assert back.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   rtol=2 ** -7)
    # Quantized entries are engine-side: host compress is the identity.
    wire, ctx = Compression.int8.compress(x)
    assert wire is x and ctx is None


# ---------------------------------------------------------------------------
# precision resolution (the fall-back-to-fp32 gates)
# ---------------------------------------------------------------------------

def test_resolve_precision_gates():
    import jax.numpy as jnp
    cfg = hvd.global_state().config
    cfg.quant_min_bytes = 1024
    rp = R.resolve_precision
    f32, i32 = jnp.float32, jnp.int32
    assert rp("int8", hvd.Sum, f32, 1 << 20, cfg, 8) == "int8"
    assert rp("int8", hvd.Sum, f32, 512, cfg, 8) == "fp32"     # floor
    assert rp("int8", hvd.Sum, i32, 1 << 20, cfg, 8) == "fp32"  # int payload
    assert rp("int8", hvd.Min, f32, 1 << 20, cfg, 8) == "fp32"  # non-sum
    assert rp("int8", hvd.Sum, f32, 1 << 20, cfg, 1) == "fp32"  # no wire
    assert rp("int8", hvd.Sum, f32, 1 << 20, cfg, 512) == "fp32"  # overflow
    assert rp("bf16", hvd.Sum, jnp.bfloat16, 1 << 20, cfg, 8) == "fp32"
    assert rp("bf16", hvd.Average, f32, 64, cfg, 8) == "bf16"  # no floor
    cfg.wire_precision = "int8"   # engine default applies when unset
    assert rp("", hvd.Sum, f32, 1 << 20, cfg, 8) == "int8"
    with pytest.raises(ValueError):
        rp("int4", hvd.Sum, f32, 1 << 20, cfg, 8)


def test_adasum_never_quantizes():
    cfg = hvd.global_state().config
    import jax.numpy as jnp
    assert R.resolve_precision("int8", hvd.Adasum, jnp.float32,
                               1 << 20, cfg, 8) == "fp32"


# ---------------------------------------------------------------------------
# wire cost model — the acceptance anchor for effective bandwidth
# ---------------------------------------------------------------------------

def test_wire_cost_model_meets_bandwidth_target():
    """int8 wire must save >= 1.5x interconnect bytes vs the fp32 ring at
    >= 4 MB payloads (the EQuARX-style effective-bandwidth claim; the
    measured-wall-clock companion lives in collective_bench —
    byte-width-insensitive CPU collectives cannot show it, a real
    interconnect does)."""
    for nbytes in (1 << 22, 1 << 24, 1 << 26):
        fp32 = R.ring_wire_bytes("fp32", nbytes, 8)
        for mode, floor in (("int8", 1.5), ("fp8", 1.5), ("bf16", 1.9)):
            saving = fp32 / R.ring_wire_bytes(mode, nbytes, 8)
            assert saving >= floor, (mode, nbytes, saving)
    # model sanity: one rank has no wire; scales shrink the saving at
    # small blocks but never below the 16-bit container's 2.66x ceiling.
    assert R.ring_wire_bytes("int8", 1 << 22, 1) == 0
    assert R.ring_wire_bytes("int8", 1 << 22, 8, block=64) > \
        R.ring_wire_bytes("int8", 1 << 22, 8, block=512)


def test_wire_saved_counter_accounts():
    from horovod_tpu.obs import REGISTRY
    before = _saved_total()
    rng = np.random.RandomState(5)
    parts = [rng.randn(70000).astype(np.float32) for _ in range(N)]
    hvd.to_numpy(C.allreduce(hvd.per_rank(parts), hvd.Sum,
                             precision="int8"))
    assert _saved_total() > before


def _saved_total() -> float:
    import horovod_tpu as hvd
    for fam in hvd.metrics():
        if fam["name"] == "hvd_wire_bytes_saved_total":
            return sum(s["value"] for s in fam["samples"])
    return 0.0


# ---------------------------------------------------------------------------
# adasum on the decomposed combine hook
# ---------------------------------------------------------------------------

def test_adasum_matches_dense_reference():
    """The shard-distributed tree (all_to_all + psum'd dots) must match
    the dense pairwise reference to fp32 tolerance."""
    rng = np.random.RandomState(11)
    vecs = [rng.randn(1003).astype(np.float32) for _ in range(N)]

    def pair(a, b):
        dot, na, nb = float(a @ b), float(a @ a), float(b @ b)
        ca = 1 - dot / (2 * na) if na > 0 else 1.0
        cb = 1 - dot / (2 * nb) if nb > 0 else 1.0
        return (ca * a + cb * b).astype(np.float32)

    ref = list(vecs)
    while len(ref) > 1:
        nxt = [pair(ref[i], ref[i + 1]) for i in range(0, len(ref) - 1, 2)]
        if len(ref) % 2:
            nxt.append(ref[-1])
        ref = nxt
    got = hvd.to_numpy(hvd.allreduce(hvd.per_rank(vecs), hvd.Adasum))
    np.testing.assert_allclose(got, ref[0], rtol=1e-4, atol=1e-5)


def test_in_context_quantized_allreduce():
    """optim/distributed's in-graph path: shared-scale quantize + narrow
    psum inside a mapped context."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    state = hvd.global_state()
    mesh, axis = state.mesh, state.config.dp_axis_name

    def kern(v):
        return R.in_context_allreduce(v[0], axis, "int8", average=True)[None]

    fn = jax.jit(shard_map(kern, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False))
    rng = np.random.RandomState(13)
    parts = np.stack([rng.randn(700).astype(np.float32) for _ in range(N)])
    out = np.asarray(fn(hvd.per_rank(list(parts))))
    exact = parts.mean(0)
    gmax = np.abs(parts).max()
    for row in out:
        np.testing.assert_allclose(row, exact,
                                   atol=1.5 * (N + 1) * gmax / 254.0)
