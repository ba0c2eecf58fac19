"""Kimi Delta Attention (KDA): the gated delta rule with a per-channel
decay, chunkwise-parallel.

Per head, with a state ``S`` in ``R^{K x V}``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,                alpha_t = exp(g_t)  (g_t <= 0, per channel)

(Kimi Linear, arXiv:2510.26692; the delta rule of Yang et al.,
arXiv:2406.06484, with Mamba-style fine-grained gates.)  Token by token
this is ``S`` sequential rank-one updates; :func:`chunk_kda` computes the
same thing a chunk of ``C`` tokens at a time.  With ``G`` the inclusive
running sum of ``g`` inside a chunk and ``S0`` the state the chunk starts
from::

    A[t,s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      s <  t
    B[t,s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])      s <= t
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S0)
    O  = (Q * exp(G)) S0 + B U
    S1 = Diag(exp(G_C)) S0 + (K * exp(G_C - G))^T U

so a chunk is one unit-lower-triangular solve and matrix products, and
only the ``K x V`` state crosses chunks.

**Strong decays.**  ``exp(G_t - G_s)`` cannot be had as
``exp(G_t) * exp(-G_s)``: with decays as strong as the published
initialisation allows, ``-G_s`` passes 88 (float32's largest exponent)
inside one 64-token chunk.  Here the pairs are split by a binary tree
over the chunk instead of by sub-chunks: the pair ``(t, s)`` belongs to
the one level ``m`` in 1, 2, 4, .. C/2 at which ``t`` lies in the upper
and ``s`` in the lower half of the same block of ``2m`` tokens, and at
that level both are rebased on ``R``, the running sum at the upper half's
first token: ``exp(G_t - R) * exp(R - G_s)``, both exponents at most 0.
Each level is one masked ``[C, K] x [K, C]`` product; nothing overflows,
nothing is clamped away, and no pair is computed elementwise.  The solve
uses the same tree: ``T <- T - T (N * mask_m) T`` merges the inverses of
two blocks of ``m`` into that of their block of ``2m`` (block forward
substitution, products only).

The forward is a Pallas kernel (``hvd_kda_fwd``): one grid row per
(sequence, head), the chunks on an ``arbitrary`` axis, the float32 state
in VMEM scratch all the way, so that it never goes to HBM between chunks.
The backward is the autodiff of :func:`chunk_kda_jnp`, the same
arithmetic as a ``lax.scan`` over chunks in plain ``jnp``, under the same
``custom_vjp`` (a reverse kernel is future work; the kernel therefore
writes no per-chunk states).  Off the TPU the kernel runs interpreted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _levels(chunk: int) -> tuple:
    assert chunk >= 2 and chunk & (chunk - 1) == 0, chunk
    return tuple(1 << i for i in range(chunk.bit_length() - 1))


def _level_mask(t, s, m: int):
    """``t`` in the upper, ``s`` in the lower half of one block of 2m."""
    return ((t // (2 * m) == s // (2 * m))
            & (t % (2 * m) >= m) & (s % (2 * m) < m))


def _rebase(G, m: int):
    """For every row, ``G`` at the first token of the upper half of the
    row's block of ``2m``: ``[.., C, K] -> [.., C, K]``."""
    *lead, C, K = G.shape
    blocks = G.reshape(*lead, C // (2 * m), 2 * m, K)
    R = jnp.broadcast_to(blocks[..., m:m + 1, :], blocks.shape)
    return R.reshape(G.shape)


def _mm(a, b, eq: str):
    return jnp.einsum(eq, a, b, precision=_HI, preferred_element_type=F32)


def _chunk_step(S0, q, k, v, g, beta):
    """One chunk for every (sequence, head) at once.  ``S0 [.., K, V]``;
    ``q, k, g [.., C, K]``; ``v [.., C, V]``; ``beta [.., C]``; all
    float32.  Returns ``(S1, O)``."""
    C = q.shape[-2]
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _mm((s <= t).astype(F32), g, "ts,...sk->...tk")
    A = jnp.zeros(q.shape[:-2] + (C, C), F32)
    Bm = _mm(q, k, "...tk,...sk->...ts") * (s == t)
    masks = {m: _level_mask(t, s, m).astype(F32) for m in _levels(C)}
    for m in _levels(C):
        R = _rebase(G, m)
        up = jnp.exp(jnp.minimum(G - R, 0.0))
        kl = k * jnp.exp(jnp.minimum(R - G, 0.0))
        A = A + masks[m] * _mm(k * up, kl, "...tk,...sk->...ts")
        Bm = Bm + masks[m] * _mm(q * up, kl, "...tk,...sk->...ts")
    N = beta[..., None] * A
    T = jnp.broadcast_to((s == t).astype(F32), N.shape)
    for m in _levels(C):
        T = T - _mm(T, _mm(N * masks[m], T, "...tj,...js->...ts"),
                    "...tj,...js->...ts")
    rhs = beta[..., None] * (v - _mm(k * jnp.exp(G), S0, "...tk,...kv->...tv"))
    U = _mm(T, rhs, "...ts,...sv->...tv")
    O = _mm(q * jnp.exp(G), S0, "...tk,...kv->...tv") \
        + _mm(Bm, U, "...ts,...sv->...tv")
    Gc = G[..., -1:, :]
    S1 = jnp.swapaxes(jnp.exp(Gc), -1, -2) * S0 \
        + _mm(k * jnp.exp(Gc - G), U, "...tk,...tv->...kv")
    return S1, O


def chunk_kda_jnp(q, k, v, g, beta, chunk: int = CHUNK, group: int = 16):
    """The chunked form in plain ``jnp``: a ``lax.scan`` over chunks,
    every (sequence, head) batched inside a step.  ``q, k, g [B, S, H,
    K]``, ``v [B, S, H, V]``, ``beta [B, S, H]``; ``g`` is the log decay
    (at most 0).  Returns ``o [B, S, H, V]`` in ``v``'s dtype.

    Written for its gradient, which is the backward of :func:`chunk_kda`:
    a step slices its chunk out of the operands where they lie (no
    chunk-major float32 copy of them is made), and the scan is
    checkpointed at two levels, ``group`` chunks inside a checkpointed
    outer step, so that the backward keeps ``n / group + group`` states
    instead of ``n`` (at B4 x S8192 x H32: 0.2 GB, not 1.07) for one more
    run of the forward."""
    B, S, H, K = q.shape
    if S % chunk:
        raise ValueError(f"sequence {S} is not a multiple of chunk {chunk}")
    n = S // chunk
    group = next(c for c in range(min(group, n), 0, -1) if n % c == 0)

    def one(S0, i):
        part = lambda x: jnp.swapaxes(lax.dynamic_slice_in_dim(
            x, i * chunk, chunk, axis=1), 1, 2).astype(F32)    # [B, H, C, X]
        S1, O = _chunk_step(S0, part(q), part(k), part(v), part(g),
                            part(beta[..., None])[..., 0])
        return S1, jnp.swapaxes(O, 1, 2).astype(v.dtype)        # [B, C, H, V]

    def some(S0, ids):
        return lax.scan(jax.checkpoint(one), S0, ids)

    S0 = jnp.zeros((B, H, K, v.shape[-1]), F32)
    _, o = lax.scan(jax.checkpoint(some), S0,
                    jnp.arange(n).reshape(n // group, group))
    # o [n / group, group, B, C, H, V]
    return jnp.moveaxis(o.reshape(n, B, chunk, H, -1), 0, 1).reshape(v.shape)


def recurrent_kda(q, k, v, g, beta):
    """The recurrence itself, token by token (float32): what the chunked
    forms are tested against."""
    B, S, H, K = q.shape
    f = lambda x: jnp.moveaxis(x.astype(F32), 1, 0)

    def step(St, x):
        qt, kt, vt, gt, bt = x                     # [B, H, .]
        St = jnp.exp(gt)[..., None] * St
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, St,
                                             precision=_HI))
        St = St + kt[..., None] * u[..., None, :]
        return St, jnp.einsum("bhk,bhkv->bhv", qt, St, precision=_HI)

    S0 = jnp.zeros((B, H, K, v.shape[-1]), F32)
    _, o = lax.scan(step, S0, (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


# ---------------------------------------------------------------------------
# the forward kernel
# ---------------------------------------------------------------------------

def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """float32 operands at full precision, on the MXU."""
    return lax.dot_general(a, b, dims, precision=_HI,
                           preferred_element_type=F32)


_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state, *,
                chunk: int):
    from jax.experimental import pallas as pl

    C = chunk
    h = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _reset():
        state[...] = jnp.zeros_like(state)

    q = q_ref[...].astype(F32)                      # [C, K]
    k = k_ref[...].astype(F32)
    v = v_ref[...].astype(F32)                      # [C, V]
    g = g_ref[...].astype(F32)
    # beta arrives as the chunk's [C, H] rows; keep this head's column.
    col = lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
    beta = jnp.sum(jnp.where(col == h, beta_ref[...].astype(F32), 0.0),
                   axis=1, keepdims=True)           # [C, 1]
    S0 = state[...]

    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (s == t).astype(F32)
    G = _dot((s <= t).astype(F32), g)
    A = jnp.zeros((C, C), F32)
    Bm = _dot(q, k, _NT) * eye
    masks = {m: _level_mask(t, s, m).astype(F32) for m in _levels(C)}
    for m in _levels(C):
        # R: G at row (t // 2m) * 2m + m, picked by a one-hot product.
        R = _dot((s == (t // (2 * m)) * (2 * m) + m).astype(F32), G)
        up = jnp.exp(jnp.minimum(G - R, 0.0))
        kl = k * jnp.exp(jnp.minimum(R - G, 0.0))
        A = A + masks[m] * _dot(k * up, kl, _NT)
        Bm = Bm + masks[m] * _dot(q * up, kl, _NT)
    N = beta * A
    T = eye
    for m in _levels(C):
        T = T - _dot(T, _dot(N * masks[m], T))
    eG = jnp.exp(G)
    U = _dot(T, beta * (v - _dot(k * eG, S0)))
    o_ref[...] = (_dot(q * eG, S0) + _dot(Bm, U)).astype(o_ref.dtype)
    Gc = G[C - 1:C, :]                              # [1, K]
    # Diag(exp(Gc)) S0: scale row c of S0 by exp(Gc[c]); as a product
    # with the diagonal matrix, so that no [1, K] -> [K, 1] relayout is
    # asked for.
    K = q.shape[1]
    kk = (lax.broadcasted_iota(jnp.int32, (K, K), 0)
          == lax.broadcasted_iota(jnp.int32, (K, K), 1))
    decay = jnp.where(kk, jnp.broadcast_to(jnp.exp(Gc), (K, K)), 0.0)
    state[...] = _dot(decay, S0) + _dot(k * jnp.exp(Gc - G), U, _TN)


def _kda_forward(q, k, v, g, beta, *, chunk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, K = q.shape
    V = v.shape[-1]
    if S % chunk:
        raise ValueError(f"sequence {S} is not a multiple of chunk {chunk}")
    # [B, S, H, X] read as [B, S, H*X]: head h of a chunk is the block
    # (chunk, X) at column block h, so nothing is transposed in HBM.
    flat = lambda x: x.reshape(B, S, -1)
    blk = lambda X: pl.BlockSpec((None, chunk, X), lambda b, h, n: (b, n, h))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(B, H, S // chunk),
        in_specs=[blk(K), blk(K), blk(V), blk(K),
                  pl.BlockSpec((None, chunk, H), lambda b, h, n: (b, n, 0))],
        out_specs=blk(V),
        out_shape=jax.ShapeDtypeStruct((B, S, H * V), v.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hvd_kda_fwd",
    )(flat(q), flat(k), flat(v), flat(g.astype(F32)), beta.astype(F32))
    return out.reshape(B, S, H, V)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _off_tpu() -> bool:
    """Where the kernel has to run interpreted (a test or a compile for a
    described chip patches this, as ``llama._flash_backend``)."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def chunk_kda(q, k, v, g, beta, chunk: int = CHUNK,
              interpret: Optional[bool] = None):
    """``o`` of the recurrence above.  ``q, k, g [B, S, H, K]`` (``g`` the
    log decay), ``v [B, S, H, V]``, ``beta [B, S, H]`` -> ``[B, S, H, V]``
    in ``v``'s dtype.  ``interpret`` None: interpreted off the TPU."""
    if interpret is None:
        interpret = _off_tpu()
    return _kda_forward(q, k, v, g, beta, chunk=chunk, interpret=interpret)


def _fwd_rule(q, k, v, g, beta, chunk, interpret):
    return chunk_kda(q, k, v, g, beta, chunk, interpret), (q, k, v, g, beta)


def _bwd_rule(chunk, interpret, res, do):
    _, vjp = jax.vjp(functools.partial(chunk_kda_jnp, chunk=chunk), *res)
    return vjp(do)


chunk_kda.defvjp(_fwd_rule, _bwd_rule)

