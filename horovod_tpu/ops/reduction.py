"""Reduction algebra: pluggable wire precision for the collective engine.

This layer unifies what were three disjoint mechanisms —
``ops/compression.py``'s dtype-casting (applied only by the torch/tf
wrapper layers), ``ops/adasum.py``'s bespoke combine tree, and the
engine's implicit fp32 ``psum`` — behind one interface:

    wire_encode(x)  -> (wire, scales)   # what goes on the interconnect
    combine(parts)  -> accumulated      # how contributions reduce (fp32)
    wire_decode(w, scales) -> tensor    # back to math precision

and builds one compiled allreduce program per (mesh, axis, mode, dtype,
shape) signature, the same way ``_build_adasum`` always did.  The engine
dispatches through :func:`build_allreduce`; everything here is traced
inside a single ``shard_map`` kernel so XLA fuses the quantize /
dequantize arithmetic with the collectives.

Wire modes (``HOROVOD_TPU_WIRE_PRECISION`` / ``hvd.allreduce(t,
compression=...)``):

``fp32``
    The implicit default: one full-precision ``psum``.
``bf16`` / ``fp16``
    Cast-down wire (the old ``Compression.fp16`` semantics, now on the
    engine hot path): cast -> psum -> cast back.  2x wire bytes saved.
``int8`` / ``fp8``
    Block-scaled quantized allreduce after EQuARX (arXiv:2506.17615),
    kept decomposed per HiCCL (arXiv:2408.05962) so precision and
    topology compose: reduce-scatter -> accumulate -> allgather.

    1. per-block absmax, then ``pmax`` across ranks so every rank
       quantizes with the *shared* scale (tiny wire: 4B/block);
    2. quantize into a narrow accumulation container — int8 payloads sum
       in int16 where the sums are *exact* (up to n=256); fp8 payloads
       sum in fp16, exact only up to fp16 rounding (~2^-11 relative per
       add, dwarfed by e4m3's own 2^-4 quantization error) — so the
       reduce-scatter is a plain ``psum_scatter`` of the narrow
       container (2B/elem on the wire);
    3. dequant-accumulate in fp32 on the owning shard (+ average);
    4. re-quantize the reduced shard with *local* per-block scales and
       ``all_gather`` the 1-byte payload + scales.

    Wire cost ~(3 + 8/block) bytes/elem round trip vs 8 for fp32 —
    ~2.6x effective bandwidth at the default block of 512.  Headroom:
    the int16 container holds sum(n * 127) exactly up to n=256 ranks
    (fp16: n=146 for fp8's +/-448 grid); :func:`resolve_precision`
    refuses quantized modes beyond that.

When NOT to quantize: reductions whose math is not a per-element sum.
Adasum's dot-products amplify correlated quantization error (its
algebra below is deliberately full-precision on the wire), MIN/MAX
would return the quantization grid, and integer payloads must stay
exact.  :func:`resolve_precision` enforces all of this, plus a size
floor (``quant_min_bytes``) under which the scale traffic and the
encode pass are not worth it.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import REGISTRY as _obs

# Engine-visible wire precision modes ("" = unset -> config default).
MODES = ("fp32", "bf16", "fp16", "int8", "fp8")
# Modes that quantize (vs merely cast): these get the block-scaled path
# and the quant_min_bytes size floor.
QUANT_MODES = ("int8", "fp8")

_m_wire_saved = _obs.counter(
    "hvd_wire_bytes_saved_total",
    "interconnect bytes saved by wire-precision modes vs an fp32 ring "
    "allreduce of the same payloads", ("mode",))
_m_wire_mode = _obs.gauge(
    "hvd_wire_precision_mode",
    "1 for the wire precision mode currently in effect as the engine "
    "default, 0 otherwise", ("mode",))


def publish_mode_gauge(active: str) -> None:
    """Reflect the engine-default wire mode in the metrics plane."""
    for m in MODES:
        _m_wire_mode.labels(mode=m).set(1.0 if m == active else 0.0)


def account_wire(mode: str, logical_bytes: int, n: int, block: int,
                 itemsize: int = 4) -> None:
    """Record bytes-saved telemetry for one dispatched allreduce.
    ``itemsize`` is the payload dtype's width — the unquantized baseline
    is that payload's own ring, not an fp32 one."""
    if not mode or mode == "fp32" or n <= 1 or logical_bytes <= 0:
        return
    saved = (ring_wire_bytes("fp32", logical_bytes, n, block, itemsize)
             - ring_wire_bytes(mode, logical_bytes, n, block, itemsize))
    if saved > 0:
        _m_wire_saved.labels(mode=mode).inc(saved)


def ring_wire_bytes(mode: str, logical_bytes: int, n: int,
                    block: int = 512, itemsize: int = 4) -> int:
    """Interconnect bytes per device for one allreduce, ring accounting.

    The NCCL-tests cost model: a ring allreduce moves ``2*(n-1)/n``
    payload widths per device (reduce-scatter + allgather halves).  Per
    element of the logical payload (width ``itemsize``) the wire carries

    - ``fp32`` (i.e. unquantized): itemsize out + itemsize back
    - ``bf16``/``fp16``: 2B out + 2B back              = 4  * (n-1)/n
    - ``int8``/``fp8``: 2B container out (int16/fp16 reduce-scatter)
      + 1B quantized back (allgather) + shared-scale pmax and gathered
      local scales (4B per block each way)             ~ (3 + 8/block)

    This is the model :mod:`benchmarks.collective_bench` reports as
    ``wire_reduction`` and the ``hvd_wire_bytes_saved_total`` counter
    integrates; it is exact for a bandwidth-bound interconnect and is
    the number that transfers to TPU (the CPU rig's shared-memory
    collectives are byte-width-insensitive — see docs/performance.md).
    """
    numel = logical_bytes // max(1, itemsize)
    frac = (n - 1) / n if n > 1 else 0.0
    if mode in ("bf16", "fp16"):
        per_elem = 4.0
    elif mode in QUANT_MODES:
        per_elem = 3.0 + 8.0 / block
    else:  # fp32 / unset: the payload's own full-precision ring
        per_elem = 2.0 * itemsize
    return int(frac * per_elem * numel)


def resolve_precision(requested: str, op: Any, dtype: Any, nbytes: int,
                      cfg, n: int) -> str:
    """Decide the wire mode for one allreduce — deterministically, from
    values every rank agrees on (op, dtype, size, synchronized config),
    so fused groups and negotiation signatures match across processes.

    ``requested`` is the per-call override (``compression=`` /
    ``entry.precision``); empty string defers to ``cfg.wire_precision``.
    Falls back to fp32 whenever the mode cannot apply losslessly-enough:
    non-float payloads, non-sum reductions (MIN/MAX/PRODUCT/ADASUM),
    single-rank meshes, sub-floor payloads (quantized modes only), and
    rank counts that would overflow the narrow accumulators.
    """
    from .collectives import ReduceOp
    mode = requested or getattr(cfg, "wire_precision", "fp32") or "fp32"
    if mode not in MODES:
        raise ValueError(
            f"unknown wire precision {mode!r}; expected one of {MODES}")
    if mode == "fp32" or n <= 1:
        return "fp32"
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        return "fp32"
    try:
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            return "fp32"
        if jnp.dtype(dtype).itemsize <= 2 and mode in ("bf16", "fp16"):
            return "fp32"  # already 16-bit: casting saves nothing
    except TypeError:
        return "fp32"
    if mode in QUANT_MODES:
        if nbytes < getattr(cfg, "quant_min_bytes", 0):
            return "fp32"
        if n > (256 if mode == "int8" else 146):
            return "fp32"  # narrow accumulator would overflow
    return mode


def as_wire_mode(compression: Any) -> str:
    """Map the public ``compression=`` argument to a wire mode string.

    Accepts mode strings (``"int8"``), the ``hvd.Compression.*``
    namespace entries (whose ``wire_mode`` attribute routes here), or
    None/``Compression.none`` for the config default.
    """
    if compression is None:
        return ""
    if isinstance(compression, str):
        if compression and compression not in MODES:
            raise ValueError(
                f"unknown wire precision {compression!r}; "
                f"expected one of {MODES}")
        return compression
    mode = getattr(compression, "wire_mode", None)
    if mode is not None:
        return mode
    raise TypeError(
        f"compression must be a mode string {MODES}, a hvd.Compression "
        f"entry, or None; got {type(compression).__name__}")


# ---------------------------------------------------------------------------
# Algebras
# ---------------------------------------------------------------------------

class ReductionAlgebra:
    """wire_encode / combine / wire_decode, traced inside the kernel.

    ``wire_encode`` maps a fp32 tensor whose last dim is the block axis
    onto (wire payload, scales-or-None); ``wire_decode`` inverts it into
    fp32; ``combine`` reduces decoded per-rank contributions (dim 0) —
    plain summation for every linear algebra, the projection tree for
    Adasum.
    """

    name = "fp32"

    def wire_encode(self, x: jax.Array):
        return x, None

    def wire_decode(self, wire: jax.Array, scales) -> jax.Array:
        return wire

    def combine(self, parts: jax.Array, axis: Optional[str] = None
                ) -> jax.Array:
        return parts.sum(0)


class CastAlgebra(ReductionAlgebra):
    """Dtype-cast wire — ``Compression.fp16``'s semantics as an algebra."""

    def __init__(self, wire_dtype, name: str) -> None:
        self.wire_dtype = wire_dtype
        self.name = name

    def wire_encode(self, x):
        return x.astype(self.wire_dtype), None

    def wire_decode(self, wire, scales):
        return wire.astype(jnp.float32)


class BlockQuantAlgebra(ReductionAlgebra):
    """Block-scaled quantization (EQuARX-style) to int8 or fp8-e4m3.

    ``wire_encode`` computes per-block absmax scales; pass
    ``shared_scale`` to quantize against a mesh-agreed scale instead (the
    reduce-scatter phase, where quantized values must sum exactly).
    """

    def __init__(self, mode: str) -> None:
        self.name = mode
        if mode == "int8":
            self.qmax = 127.0
            self.wire_dtype = jnp.int8
            self.acc_dtype = jnp.int16     # exact sums up to n=256
        elif mode == "fp8":
            self.qmax = 448.0              # f8e4m3 max normal
            self.wire_dtype = jnp.float8_e4m3fn
            # fp16 accumulation is NOT exact (ulp at 448 is 0.25, so a
            # large-|q| block can round away tiny contributions); the
            # added error is ~2^-11 relative per add, well inside e4m3's
            # own 2^-4 quantization error and the documented tolerance.
            # n<=146 bounds the magnitude, preventing overflow only.
            self.acc_dtype = jnp.float16
        else:
            raise ValueError(f"not a quantized mode: {mode!r}")

    @staticmethod
    def block_absmax(blocks: jax.Array) -> jax.Array:
        """Raw per-block absmax.  Cross-rank agreement must ``pmax``
        THIS (then :meth:`scale_from_absmax` the result) — never the
        finished scales: the 1.0 zero-block sentinel would otherwise
        dominate real small magnitudes on other ranks and quantize their
        contributions to zero."""
        return jnp.max(jnp.abs(blocks), axis=-1)

    def scale_from_absmax(self, amax: jax.Array) -> jax.Array:
        """Quantization step from (possibly mesh-agreed) absmax; 1.0 for
        all-zero blocks so encode/decode stay finite."""
        return jnp.where(amax > 0, amax / self.qmax, 1.0)

    def block_scales(self, blocks: jax.Array) -> jax.Array:
        """Local per-block scales (the allgather phase, where each rank
        owns its block outright)."""
        return self.scale_from_absmax(self.block_absmax(blocks))

    def wire_encode(self, blocks, shared_scale: Optional[jax.Array] = None):
        scale = (self.block_scales(blocks) if shared_scale is None
                 else shared_scale)
        q = blocks / scale[..., None]
        if self.wire_dtype == jnp.int8:
            q = jnp.round(q)
        # fp8: the cast itself rounds onto the e4m3 grid.
        return q.astype(self.wire_dtype), scale

    def wire_decode(self, wire, scales):
        return wire.astype(jnp.float32) * scales[..., None]


class AdasumAlgebra(ReductionAlgebra):
    """Adasum's pairwise projection combine as a reduction algebra.

    The wire stays full precision (quantization error is amplified by
    the dot-product projections — see module docstring); what this
    algebra contributes is the ``combine`` hook: the log2(n) pairwise
    tree over *shards*, with each pair's dot/norm scalars assembled from
    per-shard partials via a tiny ``psum`` — so the decomposed kernel
    never materializes all n full vectors on one device.
    """

    name = "adasum"

    def combine(self, parts: jax.Array, axis: Optional[str] = None
                ) -> jax.Array:
        vecs = [parts[i] for i in range(parts.shape[0])]
        while len(vecs) > 1:
            nxt = []
            for i in range(0, len(vecs) - 1, 2):
                nxt.append(self._pair_combine(vecs[i], vecs[i + 1], axis))
            if len(vecs) % 2:
                nxt.append(vecs[-1])
            vecs = nxt
        return vecs[0]

    @staticmethod
    def _pair_combine(a, b, axis: Optional[str]):
        """adasum(a, b) over shard-distributed vectors: partial dot/norm
        scalars reduce across the mesh axis so the projection uses the
        FULL-vector inner products, not per-shard ones."""
        a32 = a.astype(jnp.float32)
        b32 = b.astype(jnp.float32)
        partial = jnp.stack([jnp.sum(a32 * b32), jnp.sum(a32 * a32),
                             jnp.sum(b32 * b32)])
        if axis is not None:
            partial = lax.psum(partial, axis)
        dot, na, nb = partial[0], partial[1], partial[2]
        ca = jnp.where(na > 0, 1.0 - dot / (2.0 * jnp.maximum(na, 1e-30)),
                       1.0)
        cb = jnp.where(nb > 0, 1.0 - dot / (2.0 * jnp.maximum(nb, 1e-30)),
                       1.0)
        return (ca * a32 + cb * b32).astype(a.dtype)


_ALGEBRAS = {
    "fp32": ReductionAlgebra(),
    "bf16": CastAlgebra(jnp.bfloat16, "bf16"),
    "fp16": CastAlgebra(jnp.float16, "fp16"),
    "int8": BlockQuantAlgebra("int8"),
    "fp8": BlockQuantAlgebra("fp8"),
}


def algebra_for(mode: str) -> ReductionAlgebra:
    try:
        return _ALGEBRAS[mode]
    except KeyError:
        raise ValueError(
            f"unknown wire precision {mode!r}; expected one of {MODES}")


# ---------------------------------------------------------------------------
# Compiled kernel builders (one per signature, cached by ops/collectives)
# ---------------------------------------------------------------------------

def _padded_len(numel: int, n: int, block: int) -> int:
    return max(1, math.ceil(numel / (n * block))) * n * block


def build_allreduce(mesh: Mesh, axis: str, op, mode: str,
                    shape: tuple[int, ...], dtype,
                    prescale: float, postscale: float, block: int):
    """One jitted allreduce program at the given wire precision.

    Cast modes keep the single-psum shape (wire dtype is the cast).
    Quantized modes run the decomposed shared-scale pipeline described
    in the module docstring.  fp32 callers should use the plain builder
    in ops/collectives — this one assumes mode != fp32.
    """
    if mode in ("bf16", "fp16"):
        return _build_cast_allreduce(mesh, axis, op, mode, prescale,
                                     postscale)
    if mode in QUANT_MODES:
        return _build_quant_allreduce(mesh, axis, op, mode, shape, dtype,
                                      prescale, postscale, block)
    raise ValueError(f"build_allreduce: unexpected mode {mode!r}")


def _build_cast_allreduce(mesh: Mesh, axis: str, op, mode: str,
                          prescale: float, postscale: float):
    from .collectives import ReduceOp
    n = mesh.shape[axis]
    alg = algebra_for(mode)

    def kernel(v):  # [1, *shape] per device
        x = v[0]
        out_dtype = x.dtype
        if prescale != 1.0:
            x = x * jnp.asarray(prescale, x.dtype)
        wire, _ = alg.wire_encode(x)
        red = lax.psum(wire, axis)
        out = alg.wire_decode(red, None)
        if op is ReduceOp.AVERAGE:
            out = out / n
        if postscale != 1.0:
            out = out * jnp.asarray(postscale, out.dtype)
        return out.astype(out_dtype)

    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn)


def _build_quant_allreduce(mesh: Mesh, axis: str, op, mode: str,
                           shape: tuple[int, ...], dtype,
                           prescale: float, postscale: float, block: int):
    from .collectives import ReduceOp
    n = mesh.shape[axis]
    alg = algebra_for(mode)
    numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
    plen = _padded_len(numel, n, block)     # shard- and block-aligned
    nblocks = plen // block
    shard_blocks = nblocks // n

    def kernel(v):  # [1, *shape] per device
        x = v[0].astype(jnp.float32).reshape(-1)
        if prescale != 1.0:
            x = x * prescale
        if plen != numel:
            x = jnp.concatenate(
                [x, jnp.zeros((plen - numel,), jnp.float32)])
        blocks = x.reshape(nblocks, block)
        # (1) mesh-agreed scales: pmax of the RAW per-block absmax
        # (4B/block wire), then the zero-sentinel on the agreed value —
        # pmax of finished scales would let one rank's all-zero block
        # (frozen layer, joined rank's fabricated zeros) poison the
        # shared scale with its 1.0 sentinel and zero everyone else out.
        shared_scale = alg.scale_from_absmax(
            lax.pmax(alg.block_absmax(blocks), axis))
        # (2) quantize against the shared scale; with one scale per block
        # across all ranks the quantized values sum directly in the
        # narrow accumulator (exactly for int8/int16; up to fp16
        # rounding for fp8 — see class comment), so reduce-scatter is a
        # plain psum_scatter.
        q, _ = alg.wire_encode(blocks, shared_scale=shared_scale)
        acc_q = lax.psum_scatter(
            q.astype(alg.acc_dtype).reshape(-1), axis,
            scatter_dimension=0, tiled=True)              # [plen // n]
        # (3) dequant-accumulate in fp32 on the owning shard.
        me = lax.axis_index(axis)
        my_scale = lax.dynamic_slice_in_dim(
            shared_scale, me * shard_blocks, shard_blocks)
        accf = alg.wire_decode(
            acc_q.reshape(shard_blocks, block), my_scale)
        if op is ReduceOp.AVERAGE:
            accf = accf / n
        # (4) re-quantize the reduced shard with LOCAL per-block scales
        # (each rank owns its shard exactly) and allgather 1B + scales.
        w2, scale2 = alg.wire_encode(accf)
        gw = lax.all_gather(w2.reshape(-1), axis, axis=0, tiled=True)
        gs = lax.all_gather(scale2, axis, axis=0, tiled=True)
        out = alg.wire_decode(gw.reshape(nblocks, block), gs).reshape(-1)
        out = out[:numel]
        if postscale != 1.0:
            out = out * postscale
        return out.reshape(shape).astype(dtype)

    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn)


def build_decomposed_allreduce(mesh: Mesh, axis: str,
                               algebra: ReductionAlgebra,
                               shape: tuple[int, ...], dtype):
    """Generic reduce-scatter -> combine -> allgather with a pluggable
    combine hook (HiCCL's decomposition as a harness).

    The scatter half is an ``all_to_all`` of per-destination shards so
    each device holds shard *i* of every rank's vector — O(numel) memory
    per device — then ``algebra.combine`` folds the n contributions
    (receiving the mesh axis for any cross-shard scalars it needs, e.g.
    Adasum's distributed dot products), and an ``all_gather`` rebuilds
    the replicated result.  Used by :mod:`ops.adasum`; quantized sums
    take the cheaper shared-scale ``psum_scatter`` path above instead.
    """
    n = mesh.shape[axis]
    numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
    plen = max(1, math.ceil(numel / n)) * n
    shard = plen // n

    def kernel(v):  # [1, *shape] per device
        x = v[0].reshape(-1)
        if plen != numel:
            x = jnp.concatenate([x, jnp.zeros((plen - numel,), x.dtype)])
        xs = x.reshape(n, shard)
        wire, scales = algebra.wire_encode(xs)
        parts_w = lax.all_to_all(wire, axis, split_axis=0, concat_axis=0)
        parts_s = (None if scales is None else
                   lax.all_to_all(scales, axis, split_axis=0,
                                  concat_axis=0))
        parts = algebra.wire_decode(parts_w, parts_s) \
            if scales is not None else parts_w
        acc = algebra.combine(parts, axis)               # [shard]
        g = lax.all_gather(acc, axis, axis=0, tiled=True)
        return g[:numel].reshape(shape).astype(dtype)

    fn = shard_map(kernel, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# In-context form (inside an existing shard_map/pmap axis), for
# DistributedGradientTransformation's jitted train steps.
# ---------------------------------------------------------------------------

def in_context_allreduce(x: jax.Array, axis_name: str, mode: str,
                         average: bool, block: int = 512) -> jax.Array:
    """Quantized/cast allreduce of one already-mapped tensor.

    The in-graph analogue of :func:`build_allreduce` for callers already
    inside a mapped context (optim/distributed's ``_reduce_in_context``).
    Quantized modes use the shared-scale trick with a plain ``psum`` of
    the narrow accumulator (no scatter phase: in-context tensors are
    usually small per-layer gradients where the extra collective's
    latency dominates).  Wire: 2B/elem + 4B/block vs fp32's 4B.
    """
    from jax.lax import axis_size
    n = axis_size(axis_name)
    alg = algebra_for(mode)
    if mode in QUANT_MODES and n > (256 if mode == "int8" else 146):
        # Same accumulator-overflow guard the engine path applies in
        # resolve_precision: n*qmax must fit the narrow container.
        mode = "fp32"
    if mode == "fp32" or n <= 1:
        red = lax.psum(x, axis_name)
        return red / n if average else red
    if mode in ("bf16", "fp16"):
        red = alg.wire_decode(lax.psum(alg.wire_encode(x)[0], axis_name),
                              None)
        red = red / n if average else red
        return red.astype(x.dtype)
    out_dtype = x.dtype
    xf = x.astype(jnp.float32).reshape(-1)
    numel = xf.shape[0]
    plen = max(1, math.ceil(numel / block)) * block
    if plen != numel:
        xf = jnp.concatenate([xf, jnp.zeros((plen - numel,), jnp.float32)])
    blocks = xf.reshape(plen // block, block)
    # pmax the raw absmax, THEN the zero sentinel (see the kernel above).
    shared_scale = alg.scale_from_absmax(
        lax.pmax(alg.block_absmax(blocks), axis_name))
    q, _ = alg.wire_encode(blocks, shared_scale=shared_scale)
    acc = lax.psum(q.astype(alg.acc_dtype), axis_name)
    out = alg.wire_decode(acc, shared_scale).reshape(-1)[:numel]
    if average:
        out = out / n
    return out.reshape(x.shape).astype(out_dtype)
