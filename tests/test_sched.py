"""Collective schedule IR (ops/sched): lowering, signatures, resolution,
executor parity, in-jit entry points.

The load-bearing property throughout: decomposed and monolithic
allreduce are BIT-exact equals — fp32 because psum and
psum_scatter+all_gather perform the identical per-element float ops on
this backend, quantized modes by construction (chunk boundaries land on
the monolithic kernel's block boundaries, narrow-accumulator sums are
order-independent).  Parity over the real negotiated transport lives in
tests/mp_sched_worker.py / test_runner.py.
"""

import dataclasses
import json

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import sched
from horovod_tpu.ops.sched import ir

N = 8


@pytest.fixture
def sched_cfg():
    """Flip the engine default to decomposed for one test, restore after."""
    cfg = hvd.global_state().config
    old = (cfg.sched_mode, cfg.sched_chunks, cfg.quant_min_bytes)
    yield cfg
    cfg.sched_mode, cfg.sched_chunks, cfg.quant_min_bytes = old


# ---------------------------------------------------------------------------
# IR + lowering
# ---------------------------------------------------------------------------

def test_schedule_signature_stable_and_deterministic():
    a = sched.lower_allreduce(4096, 8, op_average=True, mode="fp32",
                              chunks=4, axis="hvd")
    b = sched.lower_allreduce(4096, 8, op_average=True, mode="fp32",
                              chunks=4, axis="hvd")
    assert a.signature() == b.signature()
    assert a.descriptor == "rs_ag:4"
    # Different lowering inputs -> different signatures.
    c = sched.lower_allreduce(4096, 8, op_average=True, mode="int8",
                              chunks=4, axis="hvd")
    assert c.signature() != a.signature()
    assert "int8" in c.signature()
    d = sched.lower_allreduce(4096, 8, op_average=True, mode="fp32",
                              chunks=2, axis="hvd")
    assert d.signature() != a.signature()


def test_lowered_quant_schedule_has_encode_decode_steps():
    s = sched.lower_allreduce(100000, 8, op_average=True, mode="int8",
                              chunks=2, axis="hvd", block=512)
    kinds = [st.kind for st in s.steps]
    for k in ("chunk", "encode", "reduce_scatter", "combine",
              "all_gather", "decode", "concat"):
        assert k in kinds, kinds
    # fp32 SUM has no compute step at all (nothing to combine).
    s2 = sched.lower_allreduce(4096, 8, op_average=False, mode="fp32",
                               chunks=2, axis="hvd")
    assert "combine" not in [st.kind for st in s2.steps]
    assert "encode" not in [st.kind for st in s2.steps]


def test_interleaved_order_overlaps_comm_with_compute():
    """Every chunk's reduce-scatter must be dispatched before any chunk's
    combine — the property the executor's overlap window rests on."""
    s = sched.lower_allreduce(8192, 8, op_average=True, mode="fp32",
                              chunks=4, axis="hvd")
    order = [(st.kind, st.chunk) for st in s.interleaved_order()]
    last_rs = max(i for i, (k, _) in enumerate(order)
                  if k == "reduce_scatter")
    first_cb = min(i for i, (k, _) in enumerate(order) if k == "combine")
    assert last_rs < first_cb, order
    # And per chunk, the pipeline order holds.
    for c in range(4):
        idx = {k: i for i, (k, ch) in enumerate(order) if ch == c}
        assert idx["reduce_scatter"] < idx["combine"] < idx["all_gather"]


@pytest.mark.parametrize("avg,mode", [(False, "fp32"), (True, "fp32"),
                                      (True, "int8"), (False, "fp8")])
def test_interleaved_order_matches_executor_walk(avg, mode):
    """The executor's hand-sorted dispatch-unit order must equal
    interleaved_order projected onto rs/combine/ag — the equivalence the
    walk's comment in executor.py cites.  The fp32 SUM case is the
    regression: its all_gathers become ready while later reduce-scatters
    are still pending, and a plain COMM-first priority would serialize
    the walk into RS(c), AG(c) pairs with zero overlap window."""
    s = sched.lower_allreduce(16384, 4, op_average=avg, mode=mode,
                              chunks=4, axis="hvd")
    has_combine = mode in ("int8", "fp8") or avg
    executor_order = [(u, c) for c in range(s.chunks)
                      for u in ("rs", "combine", "ag")
                      if u != "combine" or has_combine]
    executor_order.sort(key=lambda uc: (0 if uc[0] == "rs" else 1, uc[1],
                                        0 if uc[0] == "combine" else 1))
    unit = {"reduce_scatter": "rs", "combine": "combine",
            "all_gather": "ag"}
    ir_order = [(unit[st.kind], st.chunk)
                for st in s.interleaved_order() if st.kind in unit]
    assert ir_order == executor_order, (mode, avg, ir_order)
    last_rs = max(i for i, (u, _) in enumerate(ir_order) if u == "rs")
    first_post = min(i for i, (u, _) in enumerate(ir_order) if u != "rs")
    assert last_rs < first_post, ir_order


def test_schedule_validation_rejects_malformed():
    with pytest.raises(ir.ScheduleError):
        ir.Schedule(name="x", steps=(
            ir.Step(uid=0, kind="nonsense"),), chunks=1, mode="fp32")
    with pytest.raises(ir.ScheduleError):  # dangling/forward dep
        ir.Schedule(name="x", steps=(
            ir.Step(uid=0, kind="reduce_scatter", deps=(1,)),
            ir.Step(uid=1, kind="all_gather"),), chunks=1, mode="fp32")
    with pytest.raises(ir.ScheduleError):  # duplicate uid
        ir.Schedule(name="x", steps=(
            ir.Step(uid=0, kind="barrier"),
            ir.Step(uid=0, kind="barrier"),), chunks=1, mode="fp32")


def test_chunk_layout_alignment_and_degradation():
    # fp32: units of n; spread deterministically, covers >= numel.
    lay = sched.chunk_layout(1000, 8, 4, "fp32", 512)
    assert sum(lay) >= 1000 and all(l % 8 == 0 for l in lay)
    assert lay == sched.chunk_layout(1000, 8, 4, "fp32", 512)
    # quant: units of n*block, so shard boundaries land on the SAME block
    # boundaries the monolithic kernel pads to (bit-exactness invariant).
    layq = sched.chunk_layout(100000, 8, 2, "int8", 512)
    assert all(l % (8 * 512) == 0 for l in layq)
    from horovod_tpu.ops.reduction import _padded_len
    assert sum(layq) == _padded_len(100000, 8, 512)
    # Tiny payload: degrades below the requested chunk count (one unit
    # per chunk at most; a sub-unit payload gets exactly one chunk).
    assert sched.chunk_layout(10, 8, 4, "fp32", 512) == [8, 8]
    assert len(sched.chunk_layout(7, 8, 4, "fp32", 512)) == 1


def test_parse_descriptor():
    assert sched.parse_descriptor("rs_ag:4") == 4
    assert sched.parse_descriptor("rs_ag:0") is None
    assert sched.parse_descriptor("banana") is None
    assert sched.parse_descriptor("") is None
    assert sched.descriptor(2) == "rs_ag:2"


def test_parse_compiled_descriptor():
    assert sched.parse_compiled_descriptor("compiled:rs_ag:4") == 4
    assert sched.parse_compiled_descriptor("compiled:rs_ag:0") is None
    assert sched.parse_compiled_descriptor("rs_ag:4") is None
    assert sched.parse_compiled_descriptor("compiled:hier:2:2") is None
    assert sched.parse_compiled_descriptor("") is None
    assert sched.compiled_descriptor(2) == "compiled:rs_ag:2"
    assert sched.known_descriptor("compiled:rs_ag:2")
    # The dispatched parser must NOT claim compiled descriptors.
    assert sched.parse_descriptor("compiled:rs_ag:4") is None


def test_resolve_schedule_gates(sched_cfg):
    from horovod_tpu.ops.collectives import ReduceOp
    cfg = sched_cfg
    cfg.sched_mode, cfg.sched_chunks = "decomposed", 4
    ok = dict(verb="allreduce", op=ReduceOp.AVERAGE, dtype=np.float32,
              nbytes=1 << 20, cfg=cfg, n=8, mode="fp32")

    def res(**kw):
        a = {**ok, **kw}
        return sched.resolve_schedule(a.pop("requested", ""), a["verb"],
                                      a["op"], a["dtype"], a["nbytes"],
                                      a["cfg"], a["n"], a["mode"])
    assert res() == "rs_ag:4"
    assert res(requested="monolithic") == ""
    assert res(requested="rs_ag:2") == "rs_ag:2"
    assert res(verb="allgather") == ""
    assert res(op=ReduceOp.MAX) == ""
    assert res(op=ReduceOp.ADASUM) == ""
    assert res(dtype=np.int32) == ""
    assert res(n=1) == ""
    assert res(nbytes=16) == ""          # too small to cut into 2 chunks
    # Cast wire modes keep the single-psum shape: decomposing them would
    # either re-round the combined shard (diverging from monolithic) or
    # gather at 4 bytes while claiming 2-byte savings.  The executor
    # refuses them outright as the backstop.
    assert res(mode="bf16") == ""
    assert res(mode="fp16") == ""
    from horovod_tpu.ops.sched import executor as SE
    with pytest.raises(ValueError, match="cast wire mode"):
        SE.execute_allreduce(
            [hvd.per_rank([np.ones((64,), np.float32)] * N)], hvd.Sum,
            descriptor="rs_ag:2", precision="bf16")
    with pytest.raises(ValueError):
        res(requested="bogus")
    # Hierarchical flag composes with decomposition.  Single-controller
    # topology detection sees local_size == world size (no tier), so the
    # flag alone keeps the flat descriptor; a valid explicit split
    # upgrades decomposed requests to the chunked+tiered family.
    cfg.hierarchical_allreduce = True
    old_ls = cfg.hierarchical_local_size
    try:
        assert res() == "rs_ag:4"              # invalid split -> flat
        cfg.hierarchical_local_size = 4
        assert res() == "hier:4:4"
        assert res(requested="rs_ag:2") == "hier:4:2"   # upgrade
        assert res(requested="monolithic") == ""  # unchunked kernel path
        # Quantized cross hop tightens the size gate to block units.
        cfg.hierarchical_cross_precision = "int8"
        assert res() == "hier:4:4"
        assert res(nbytes=4 * 8 * 512) == ""   # < 2 block-aligned units
        cfg.hierarchical_cross_precision = ""
    finally:
        cfg.hierarchical_allreduce = False
        cfg.hierarchical_local_size = old_ls
    # Explicit hier requests pass through without the flag; an invalid
    # split degrades to the flat descriptor at the same chunk count.
    assert res(requested="hier:4:2") == "hier:4:2"
    assert res(requested="hier:3:2") == "rs_ag:2"   # 8 % 3 != 0
    assert res(requested="hier:8:2") == "rs_ag:2"   # n_local == n
    # Default config: monolithic.
    cfg.sched_mode = "monolithic"
    assert res() == ""


def test_resolve_schedule_compiled(sched_cfg):
    """The compiled mode shares every eligibility gate with decomposed
    (same chunk_layout, same verb/op/dtype/size rules) and differs only
    in the descriptor family it emits — except under a hierarchical
    split, where it deterministically falls back to the DISPATCHED
    ``hier:*`` family (no compiled tiered lowering yet; ISSUE 16)."""
    from horovod_tpu.ops.collectives import ReduceOp
    cfg = sched_cfg
    cfg.sched_mode, cfg.sched_chunks = "compiled", 4
    ok = dict(verb="allreduce", op=ReduceOp.AVERAGE, dtype=np.float32,
              nbytes=1 << 20, cfg=cfg, n=8, mode="fp32")

    def res(**kw):
        a = {**ok, **kw}
        return sched.resolve_schedule(a.pop("requested", ""), a["verb"],
                                      a["op"], a["dtype"], a["nbytes"],
                                      a["cfg"], a["n"], a["mode"])
    assert res() == "compiled:rs_ag:4"
    assert res(requested="compiled") == "compiled:rs_ag:4"
    assert res(requested="compiled:rs_ag:2") == "compiled:rs_ag:2"
    # Explicit requests for the other backends still win per call.
    assert res(requested="monolithic") == ""
    assert res(requested="rs_ag:2") == "rs_ag:2"
    # Identical gates to decomposed.
    assert res(verb="allgather") == ""
    assert res(op=ReduceOp.MAX) == ""
    assert res(dtype=np.int32) == ""
    assert res(n=1) == ""
    assert res(nbytes=16) == ""
    assert res(mode="bf16") == ""
    assert res(mode="fp16") == ""
    # Hierarchical split: deterministic fallback to the dispatched
    # chunked+tiered family at the SAME chunk count (logged once).
    cfg.hierarchical_allreduce = True
    old_ls = cfg.hierarchical_local_size
    try:
        cfg.hierarchical_local_size = 4
        assert res() == "hier:4:4"
        assert res(requested="compiled:rs_ag:2") == "hier:4:2"
    finally:
        cfg.hierarchical_allreduce = False
        cfg.hierarchical_local_size = old_ls
    # Without the split the compiled family survives the flag.
    assert res() == "compiled:rs_ag:4"


# ---------------------------------------------------------------------------
# Executor parity (single-controller; negotiated-transport parity is the
# mp worker's job)
# ---------------------------------------------------------------------------

def _parts(numel, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(numel).astype(np.float32) for _ in range(N)]


def test_decomposed_bit_exact_fp32(sched_cfg):
    parts = _parts(5000)
    x = hvd.per_rank(parts)
    ref = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 4
    got = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    assert np.array_equal(ref, got)          # BIT-exact, not allclose
    # SUM too (no combine step in the schedule).
    sched_cfg.sched_mode = "monolithic"
    ref_s = hvd.to_numpy(hvd.allreduce(x, hvd.Sum))
    sched_cfg.sched_mode = "decomposed"
    got_s = hvd.to_numpy(hvd.allreduce(x, hvd.Sum))
    assert np.array_equal(ref_s, got_s)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_decomposed_bit_exact_quantized(sched_cfg, mode):
    """Chunked quantized pipeline == monolithic quantized kernel, bit for
    bit: same block layout, exact narrow-accumulator sums, same per-block
    requantization — chunking must not change a single ulp."""
    sched_cfg.quant_min_bytes = 0
    parts = _parts(100000, seed=3)
    x = hvd.per_rank(parts)
    sched_cfg.sched_mode = "monolithic"
    ref = hvd.to_numpy(hvd.allreduce(x, hvd.Average, compression=mode))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 3
    got = hvd.to_numpy(hvd.allreduce(x, hvd.Average, compression=mode))
    assert np.array_equal(ref, got)
    # And the quantized path really ran (lossy vs exact numpy).
    exact = np.stack(parts).mean(0)
    assert np.abs(got - exact).max() > 0


def test_decomposed_grouped_and_prepost_scale(sched_cfg):
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 2
    xs = [hvd.per_rank([np.full((97,), float(r + i), np.float32)
                        for r in range(N)]) for i in range(3)]
    outs = hvd.grouped_allreduce(xs, hvd.Sum)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(
            hvd.to_numpy(o), np.full((97,), sum(range(N)) + N * i))
    # prescale/postscale ride the rs/ag phases.
    from horovod_tpu.ops import collectives as C
    x = hvd.per_rank(_parts(4096, seed=5))
    sched_cfg.sched_mode = "monolithic"
    ref = hvd.to_numpy(C.allreduce(x, hvd.Sum, prescale_factor=0.5,
                                   postscale_factor=2.0))
    sched_cfg.sched_mode = "decomposed"
    got = hvd.to_numpy(C.allreduce(x, hvd.Sum, prescale_factor=0.5,
                                   postscale_factor=2.0))
    assert np.array_equal(ref, got)


def test_decomposed_overlap_gauge_set(sched_cfg):
    from horovod_tpu.ops.sched.executor import _m_overlap, _m_sched
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "decomposed", 4
    before = _m_sched.labels(schedule="rs_ag:4").value
    x = hvd.per_rank(_parts(8192, seed=7))
    hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    assert _m_sched.labels(schedule="rs_ag:4").value == before + 1
    frac = _m_overlap.value
    assert 0.0 <= frac <= 1.0
    # With >= 2 chunks dispatched interleaved there is always a nonzero
    # window where a chunk's comm is in flight during another's compute.
    assert frac > 0.0


def test_overlap_fraction_math():
    from horovod_tpu.ops.sched.executor import _overlap_fraction
    assert _overlap_fraction([(0, 10)], [(2, 4)]) == pytest.approx(0.2)
    assert _overlap_fraction([(0, 10)], []) == 0.0
    assert _overlap_fraction([], [(0, 1)]) == 0.0
    assert _overlap_fraction([(0, 2), (4, 6)],
                             [(1, 5)]) == pytest.approx(0.5)
    # Overlapping compute windows count their union, not twice.
    assert _overlap_fraction([(0, 10)],
                             [(2, 4), (2, 4)]) == pytest.approx(0.2)
    assert _overlap_fraction([(0, 10)],
                             [(2, 5), (3, 6)]) == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# Compiled single-program backend (ops/sched/compiled)
# ---------------------------------------------------------------------------

def test_compiled_bit_exact_fp32(sched_cfg):
    """One jitted GSPMD program == monolithic psum, bit for bit: the
    compiled kernel inlines the executor's fp32 phase builders, and on
    this backend psum and psum_scatter+all_gather share per-element
    float-op association (the same property the decomposed test pins)."""
    parts = _parts(5000, seed=21)
    x = hvd.per_rank(parts)
    ref = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "compiled", 4
    got = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    assert np.array_equal(ref, got)          # BIT-exact, not allclose
    sched_cfg.sched_mode = "monolithic"
    ref_s = hvd.to_numpy(hvd.allreduce(x, hvd.Sum))
    sched_cfg.sched_mode = "compiled"
    got_s = hvd.to_numpy(hvd.allreduce(x, hvd.Sum))
    assert np.array_equal(ref_s, got_s)
    # And against the dispatched decomposition at the same chunk count.
    sched_cfg.sched_mode = "decomposed"
    deco = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    assert np.array_equal(got, deco)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_compiled_bit_exact_quantized(sched_cfg, mode):
    """Quantized compiled program == monolithic quantized kernel, bit
    for bit: identical n*block chunk boundaries, shared-pmax scales,
    exact narrow-accumulator psum_scatter, local requantization."""
    sched_cfg.quant_min_bytes = 0
    parts = _parts(100000, seed=23)
    x = hvd.per_rank(parts)
    sched_cfg.sched_mode = "monolithic"
    ref = hvd.to_numpy(hvd.allreduce(x, hvd.Average, compression=mode))
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "compiled", 3
    got = hvd.to_numpy(hvd.allreduce(x, hvd.Average, compression=mode))
    assert np.array_equal(ref, got)
    # The quantized path really ran (lossy vs exact numpy).
    exact = np.stack(parts).mean(0)
    assert np.abs(got - exact).max() > 0


def test_compiled_grouped_and_prepost_scale(sched_cfg):
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "compiled", 2
    xs = [hvd.per_rank([np.full((97,), float(r + i), np.float32)
                        for r in range(N)]) for i in range(3)]
    outs = hvd.grouped_allreduce(xs, hvd.Sum)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(
            hvd.to_numpy(o), np.full((97,), sum(range(N)) + N * i))
    from horovod_tpu.ops import collectives as C
    x = hvd.per_rank(_parts(4096, seed=25))
    sched_cfg.sched_mode = "monolithic"
    ref = hvd.to_numpy(C.allreduce(x, hvd.Sum, prescale_factor=0.5,
                                   postscale_factor=2.0))
    sched_cfg.sched_mode = "compiled"
    got = hvd.to_numpy(C.allreduce(x, hvd.Sum, prescale_factor=0.5,
                                   postscale_factor=2.0))
    assert np.array_equal(ref, got)


def test_compiled_counters_and_program_cache(sched_cfg):
    """The contract the CI compiled-parity job asserts at np>1: the
    compiled path takes ONE program dispatch (its own counter moves) and
    ZERO per-chunk executor dispatches; re-dispatching the same schedule
    signature is a cache hit, not a rebuild."""
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops.sched.compiled import _m_compiled
    from horovod_tpu.ops.sched.executor import _m_sched
    sched_cfg.sched_mode, sched_cfg.sched_chunks = "compiled", 4
    x = hvd.per_rank(_parts(8192, seed=27))
    before_c = _m_compiled.labels(schedule="compiled:rs_ag:4").value
    before_s = _m_sched.total()
    out1 = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    assert _m_compiled.labels(
        schedule="compiled:rs_ag:4").value == before_c + 1
    assert _m_sched.total() == before_s      # zero per-chunk dispatches
    # Same signature again: program-cache hit, no new build.
    hits0, miss0 = C._cache.hits, C._cache.misses
    out2 = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    assert C._cache.misses == miss0
    assert C._cache.hits > hits0
    assert np.array_equal(out1, out2)
    assert _m_sched.total() == before_s


def test_compiled_executor_routes_descriptor(sched_cfg):
    """executor.execute_allreduce hands compiled descriptors to the
    compiled backend — the engine's single dispatch call site never
    branches on the family itself."""
    from horovod_tpu.ops.sched import executor as SE
    x = hvd.per_rank(_parts(4096, seed=29))
    ref = hvd.to_numpy(hvd.allreduce(x, hvd.Average))
    out = SE.execute_allreduce([x], hvd.Average,
                               descriptor="compiled:rs_ag:2")
    assert np.array_equal(ref, hvd.to_numpy(out[0]))


def test_compiled_rejects_cast_modes_and_unknown_descriptors():
    from horovod_tpu.ops.sched import compiled as CP
    x = hvd.per_rank([np.ones((64,), np.float32)] * N)
    with pytest.raises(ValueError, match="cast wire mode"):
        CP.execute_allreduce([x], hvd.Sum, descriptor="compiled:rs_ag:2",
                             precision="bf16")
    with pytest.raises(ValueError, match="unknown compiled"):
        CP.execute_allreduce([x], hvd.Sum, descriptor="rs_ag:2")


def test_perfmodel_compiled_expectation():
    """The compiled arm keeps the ring's wire bytes but collapses the
    per-chunk dispatch latency: steps == one ring regardless of k."""
    from horovod_tpu.obs import perfmodel as PM
    c = PM.expected_allreduce(1 << 20, 8, chunks=4, compiled=True)
    d = PM.expected_allreduce(1 << 20, 8, chunks=4)
    assert c.schedule == "compiled:rs_ag:4"
    assert d.schedule == "rs_ag:4"
    assert c.wire_bytes == d.wire_bytes
    assert c.steps == 2 * 7 and d.steps == 2 * 7 * 4


# ---------------------------------------------------------------------------
# In-jit entry points
# ---------------------------------------------------------------------------

def test_in_context_overlap_allreduce_parity():
    import jax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = hvd.mesh()
    axis = hvd.global_state().config.dp_axis_name
    x = np.random.RandomState(11).randn(N, 3000).astype(np.float32)

    def mono(v):
        return lax.psum(v[0], axis) / N

    def deco(v):
        return sched.overlap_allreduce(v[0], axis, average=True, chunks=3)

    f1 = jax.jit(shard_map(mono, mesh=mesh, in_specs=P(axis),
                           out_specs=P(), check_vma=False))
    f2 = jax.jit(shard_map(deco, mesh=mesh, in_specs=P(axis),
                           out_specs=P(), check_vma=False))
    assert np.array_equal(np.asarray(f1(x)), np.asarray(f2(x)))
    # Quantized in-context: parity with the reduction-layer convention
    # within the documented shared-scale bound.
    def deco8(v):
        return sched.overlap_allreduce(v[0], axis, average=True,
                                       mode="int8", chunks=2, block=512)
    f3 = jax.jit(shard_map(deco8, mesh=mesh, in_specs=P(axis),
                           out_specs=P(), check_vma=False))
    got = np.asarray(f3(x))
    exact = x.mean(0)
    gmax = np.abs(x).max()
    assert np.abs(got - exact).max() <= 1.5 * (N + 1) * gmax / 254.0


def test_matmul_reducescatter_parity():
    import jax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = hvd.mesh()
    axis = hvd.global_state().config.dp_axis_name
    rng = np.random.RandomState(13)
    # Row-parallel: contraction dim sharded over the axis (one slice per
    # rank stacked on dim 0), output dim 64 divides n*chunks = 16.
    xs = rng.randn(N, 4, 32).astype(np.float32)     # per-rank [4, 32]
    w = rng.randn(N, 32, 64).astype(np.float32)     # per-rank w slice

    def mono(xv, wv):
        return lax.psum(xv[0] @ wv[0], axis)

    def fused(xv, wv):
        return sched.matmul_reducescatter(xv[0], wv[0], axis, chunks=2)

    f1 = jax.jit(shard_map(mono, mesh=mesh, in_specs=(P(axis), P(axis)),
                           out_specs=P(), check_vma=False))
    f2 = jax.jit(shard_map(fused, mesh=mesh, in_specs=(P(axis), P(axis)),
                           out_specs=P(), check_vma=False))
    assert np.array_equal(np.asarray(f1(xs, w)), np.asarray(f2(xs, w)))
    # Indivisible output dim falls back to the plain psum path.
    w_odd = rng.randn(N, 32, 60).astype(np.float32)
    f3 = jax.jit(shard_map(
        lambda xv, wv: sched.matmul_reducescatter(xv[0], wv[0], axis,
                                                  chunks=7),
        mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
        check_vma=False))
    f4 = jax.jit(shard_map(mono, mesh=mesh, in_specs=(P(axis), P(axis)),
                           out_specs=P(), check_vma=False))
    assert np.array_equal(np.asarray(f4(xs, w_odd)),
                          np.asarray(f3(xs, w_odd)))


# ---------------------------------------------------------------------------
# Engine integration: meta carries the descriptor; fusion groups split.
# ---------------------------------------------------------------------------

def test_entry_meta_carries_schedule(sched_cfg):
    from horovod_tpu.ops.engine import (TensorTableEntry,
                                        _parse_joinable_meta)
    x = hvd.per_rank([np.ones((4096,), np.float32)] * N)
    e = TensorTableEntry(name="t.sc", verb="allreduce", payload=x,
                         op=hvd.Sum, schedule="rs_ag:4")
    m = json.loads(e.meta())
    assert m["sc"] == "rs_ag:4"
    parsed = _parse_joinable_meta(e.meta())
    assert parsed is not None and parsed["sc"] == "rs_ag:4"
    # Monolithic entries omit the field: default-mode metas stay
    # byte-identical with pre-schedule-IR peers.
    e2 = TensorTableEntry(name="t.sc2", verb="allreduce", payload=x,
                          op=hvd.Sum)
    assert "sc" not in json.loads(e2.meta())
    # Unknown descriptor from a version-skewed peer: skip, don't crash.
    bad = dict(m)
    bad["sc"] = "ring_exchange:9"
    assert _parse_joinable_meta(json.dumps(bad)) is None


def test_fusion_splits_mixed_schedules(sched_cfg):
    from horovod_tpu.ops.engine import TensorTableEntry
    eng = hvd.global_state().engine
    x = hvd.per_rank([np.ones((64,), np.float32)] * N)
    entries = [
        TensorTableEntry(name=f"t.scf.{i}", verb="allreduce", payload=x,
                         op=hvd.Sum, schedule=s)
        for i, s in enumerate(["rs_ag:4", "rs_ag:4", "", "rs_ag:2"])]
    groups = eng._fuse(entries)
    keyed = sorted(tuple(e.schedule for e in g) for g in groups)
    assert keyed == [("",), ("rs_ag:2",), ("rs_ag:4", "rs_ag:4")]


def test_entry_meta_carries_compiled_schedule(sched_cfg):
    """The compiled backend choice rides the SAME ``sc`` negotiation
    field as the dispatched descriptors (wp-style contract): peers
    joining mid-run and version-skewed peers see one vocabulary."""
    from horovod_tpu.ops.engine import (TensorTableEntry,
                                        _parse_joinable_meta)
    x = hvd.per_rank([np.ones((4096,), np.float32)] * N)
    e = TensorTableEntry(name="t.csc", verb="allreduce", payload=x,
                         op=hvd.Sum, schedule="compiled:rs_ag:4")
    m = json.loads(e.meta())
    assert m["sc"] == "compiled:rs_ag:4"
    parsed = _parse_joinable_meta(e.meta())
    assert parsed is not None and parsed["sc"] == "compiled:rs_ag:4"


def test_fusion_splits_compiled_from_dispatched(sched_cfg):
    """Compiled and dispatched entries must never fuse: their payloads
    run through different executables."""
    from horovod_tpu.ops.engine import TensorTableEntry
    eng = hvd.global_state().engine
    x = hvd.per_rank([np.ones((64,), np.float32)] * N)
    entries = [
        TensorTableEntry(name=f"t.cf.{i}", verb="allreduce", payload=x,
                         op=hvd.Sum, schedule=s)
        for i, s in enumerate(
            ["compiled:rs_ag:4", "rs_ag:4", "compiled:rs_ag:4", ""])]
    groups = eng._fuse(entries)
    keyed = sorted(tuple(e.schedule for e in g) for g in groups)
    assert keyed == [("",), ("compiled:rs_ag:4", "compiled:rs_ag:4"),
                     ("rs_ag:4",)]


def test_reconcile_metas_adopts_echoed_common_mode(sched_cfg):
    """Mixed-mode peers: the coordinator echoes the lowest rank's meta
    and every rank adopts its schedule/wire fields BEFORE fusion, so all
    processes execute the same program (collective channel IDs are
    per-executable under jax.distributed — a rank running the compiled
    program against peers walking per-chunk dispatches deadlocks)."""
    from horovod_tpu.ops.engine import TensorTableEntry
    eng = hvd.global_state().engine
    x = hvd.per_rank([np.ones((4096,), np.float32)] * N)
    e = TensorTableEntry(name="t.rm", verb="allreduce", payload=x,
                         op=hvd.Sum, schedule="compiled:rs_ag:4")
    peer = TensorTableEntry(name="t.rm", verb="allreduce", payload=x,
                            op=hvd.Sum, schedule="rs_ag:4",
                            precision="int8")
    eng._reconcile_metas([e], {"t.rm": e}, {"t.rm": peer.meta()})
    assert e.schedule == "rs_ag:4"
    assert e.precision == "int8"
    # Echo of our own meta: no-op.
    e2 = TensorTableEntry(name="t.rm2", verb="allreduce", payload=x,
                          op=hvd.Sum, schedule="compiled:rs_ag:4")
    eng._reconcile_metas([e2], {"t.rm2": e2}, {"t.rm2": e2.meta()})
    assert e2.schedule == "compiled:rs_ag:4"
    # Unparseable meta from a version-skewed peer: skip, don't adopt.
    bad = json.loads(peer.meta())
    bad["sc"] = "ring_exchange:9"
    e3 = TensorTableEntry(name="t.rm3", verb="allreduce", payload=x,
                          op=hvd.Sum, schedule="compiled:rs_ag:4")
    eng._reconcile_metas([e3], {"t.rm3": e3}, {"t.rm3": json.dumps(bad)})
    assert e3.schedule == "compiled:rs_ag:4"
    # The adopted direction also runs dispatched -> compiled.
    e4 = TensorTableEntry(name="t.rm4", verb="allreduce", payload=x,
                          op=hvd.Sum, schedule="rs_ag:4")
    peer4 = TensorTableEntry(name="t.rm4", verb="allreduce", payload=x,
                             op=hvd.Sum, schedule="compiled:rs_ag:4")
    eng._reconcile_metas([e4], {"t.rm4": e4}, {"t.rm4": peer4.meta()})
    assert e4.schedule == "compiled:rs_ag:4"


def test_zero_entry_rebuilds_schedule(sched_cfg):
    """A joined rank must rebuild entries at the SAME schedule (and
    precision) the live ranks resolved, or the per-chunk dispatches
    diverge across processes."""
    eng = hvd.global_state().engine
    meta = {"v": "allreduce", "d": "float32", "s": [N, 4096], "o": "sum",
            "sc": "rs_ag:4"}
    from horovod_tpu.ops.engine import _parse_joinable_meta
    e = eng._zero_entry("t.zj", _parse_joinable_meta(json.dumps(meta)))
    assert e.schedule == "rs_ag:4"
    assert e.precision == ""
    # Compiled descriptors rebuild identically.
    meta["sc"] = "compiled:rs_ag:4"
    e2 = eng._zero_entry("t.zjc", _parse_joinable_meta(json.dumps(meta)))
    assert e2.schedule == "compiled:rs_ag:4"
