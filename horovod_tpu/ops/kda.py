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

**The running sums as one signed product.**  ``G`` and the six rebased
differences ``D_m = G - R_m`` are sums of ``g`` over runs of tokens, so
all seven are ``W g`` for one matrix ``W [7C, C]`` of 0, +1 and -1
(:func:`_signed_sums`; ``D_m[t]`` adds the tokens after ``r`` up to ``t``,
or takes away those after ``t`` up to ``r``).  ``W`` is exact in bfloat16
and ``g`` is split into three bfloat16 pieces that add up to it bit for
bit, so ``W hi + W mid + W lo`` accumulated in float32 is the product at
``highest`` without the three of its six passes that multiply by the
zero middle and low parts of ``W``.  It is the chunk's one product below
``highest``, and exact there and nowhere else: no other operand is
exact in bfloat16.  ``D_m`` as one sum also cancels less than ``G - R_m``
from two.

Both directions are Pallas kernels under one ``custom_vjp``, around one
function of values, :func:`_chunk`: ``(S0, q, k, v, g, beta) -> (S1, O)``
for one chunk of one head.  The forward (``hvd_kda_fwd``) has one grid
row per (sequence, head), the chunks on an ``arbitrary`` axis and the
float32 state in VMEM scratch all the way, so that it never goes to HBM
between chunks.  It comes in two calls of one kernel.  :func:`chunk_kda`
itself is the stateless one.  The ``custom_vjp``'s forward rule is the
state-writing one: it also stores the state each chunk starts from,
``[B, H, S / chunk, K, V]`` float32 (``S0`` before the update; the last
chunk's ``S1`` is read by nothing), as the residual the backward walks
over: 1.07 GB a layer at B4 x S8192 x H32 x K128 x V128, alive from that
layer's second forward to the end of its backward.  The rule is given to
``defvjp`` with ``optimize_remat``, so under a ``jax.checkpoint`` around
the layer the first forward, whose residuals nobody keeps, is the
stateless call and only remat's second forward writes.  Both calls
return the same ``o`` bit for bit.

The backward (``hvd_kda_bwd``) has a grid over (sequence, block of
heads, chunk) and walks the chunks last to first by its index maps.  The
state's cotangent, ``[heads in block, K, V]`` float32, lives in VMEM
scratch from the last chunk (zero there) to the first.  A grid step
loads a chunk's operands, its ``S0`` and its ``dO``, and takes
``jax.vjp`` of :func:`_chunk` inside the kernel body with cotangents
``(dS1, dO)``: the chunk's intermediates are made again and transposed,
float32 at ``highest`` but for the signed sums, and ``dq, dk, dv, dg``
(float32), ``dbeta`` and the new state cotangent are written.  Three
pieces of the chunk carry a transpose of their own (:func:`_sums`,
:func:`_pairs`, :func:`_inverse`) where autodiff's would spend products
the derivative does not need.  ``dbeta`` has a layout of its
own, ``[B, H / block, S, block]``: heads lie on a ``parallel`` axis, and
a block of ``beta``'s ``[C, H]`` rows would be written by every block of
heads.  The block of heads (:func:`_head_block`) is the most heads that
divide ``H``, keep a block's columns whole lane tiles and fit the
default scoped VMEM by :func:`_bwd_resident` (8 of the published 32).  A
grid step runs them one after another in a loop, each on its own 128-lane
columns of the blocks, so the chunk is traced and compiled once however
many they are.  Off the TPU both kernels run interpreted.
:func:`chunk_kda_jnp` is the same arithmetic as a ``lax.scan`` in plain
``jnp``: what the tests compare the kernels' gradients with, and in no
training step.
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


def chunk_kda_jnp(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked form in plain ``jnp``: a ``lax.scan`` over chunks,
    every (sequence, head) batched inside a step.  ``q, k, g [B, S, H,
    K]``, ``v [B, S, H, V]``, ``beta [B, S, H]``; ``g`` is the log decay
    (at most 0).  Returns ``o [B, S, H, V]`` in ``v``'s dtype.  The plain
    form that the kernels' results and gradients are tested against."""
    B, S, H, K = q.shape
    if S % chunk:
        raise ValueError(f"sequence {S} is not a multiple of chunk {chunk}")
    n = S // chunk

    def one(S0, i):
        part = lambda x: jnp.swapaxes(lax.dynamic_slice_in_dim(
            x, i * chunk, chunk, axis=1), 1, 2).astype(F32)    # [B, H, C, X]
        S1, O = _chunk_step(S0, part(q), part(k), part(v), part(g),
                            part(beta[..., None])[..., 0])
        return S1, jnp.swapaxes(O, 1, 2).astype(v.dtype)        # [B, C, H, V]

    S0 = jnp.zeros((B, H, K, v.shape[-1]), F32)
    _, o = lax.scan(one, S0, jnp.arange(n))                # [n, B, C, H, V]
    return jnp.moveaxis(o, 0, 1).reshape(v.shape)


def _token_step(St, x):
    """One token of the recurrence: ``St [B, H, K, V]`` and the token's
    ``(q, k, v, g, beta)`` -> the next state and ``o_t``."""
    qt, kt, vt, gt, bt = x                         # [B, H, .]
    St = jnp.exp(gt)[..., None] * St
    u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, St,
                                         precision=_HI))
    St = St + kt[..., None] * u[..., None, :]
    return St, jnp.einsum("bhk,bhkv->bhv", qt, St, precision=_HI)


def recurrent_kda(q, k, v, g, beta):
    """The recurrence itself, token by token (float32): what the chunked
    forms are tested against."""
    B, S, H, K = q.shape
    f = lambda x: jnp.moveaxis(x.astype(F32), 1, 0)
    S0 = jnp.zeros((B, H, K, v.shape[-1]), F32)
    _, o = lax.scan(_token_step, S0, (f(q), f(k), f(v), f(g), f(beta)))
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


def _own_transpose(fwd, bwd, static=()):
    """``custom_vjp`` for a piece of the chunk whose transpose is cheaper
    written out than derived: ``fwd`` returns the result and what ``bwd``
    keeps; the arguments ``static`` are Python values, handed to ``bwd``
    first."""
    def attach(primal):
        f = jax.custom_vjp(primal, nondiff_argnums=static)
        f.defvjp(fwd, bwd)
        return f
    return attach


def _iotas(n: int):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0),
            lax.broadcasted_iota(jnp.int32, (n, n), 1))


# The kernels are bound by the matrix unit, so the pieces below count its
# instructions, not FLOPs.  A float32 product at ``highest`` is six
# passes; a pass of ``[M, k] x [k, N]`` (k, N to 128) is M/8 pushes of
# eight rows of the left operand and 16 latches of the right one,
# whatever of the unit they fill (in the compiler's schedule a push
# holds one of the chip's four units for eight cycles, a latch for two).
# So: operands that share the other side are stacked (one set of
# latches); rows that a mask would zero are not pushed; what is a sum
# over lanes goes to the vector unit; and the sums of ``g``, whose left
# operand is exact in bfloat16, take the three passes that are not 0.
# By that rule a chunk of 64 at K = V = 128 is 3,408 instructions
# forward (1,264 pushes + 2,144 latches) and 6,960 in reverse (2,704 +
# 4,256); before PR 32 it was 5,568 and 9,696, which is what Mosaic's
# dump showed (now 1,208 + 2,128 and 2,600 + 4,144: a bfloat16 push is
# 16 rows).  ``tests/test_kimi_linear.py`` counts them from the jaxpr.

def _signed_sums(C: int, transposed: bool = False):
    """``W`` of 0, +1, -1 (exact in bfloat16), ``L`` the levels of the
    tree: ``[(L + 1) C, 2 C]``, the ``C`` columns twice, or transposed
    ``[C, (L + 1) C]``.  Rows 0..C-1 are the triangle ``s <= t``, so that
    ``W g`` starts with the running sum ``G``; the rows of level ``m`` are
    ``tri[t] - tri[r]`` with ``r = (t // 2m) * 2m + m`` the first token of
    the upper half of ``t``'s block, so that they give ``D_m = G - R_m``
    as one sum: +1 for ``r < s <= t``, -1 for ``t < s <= r``.  Built from
    iotas by shifts (``C`` is a power of two), level ``i >= 1`` being ``m
    = 2^(i-1)``."""
    bits = C.bit_length() - 1
    shape = (C, (bits + 1) * C) if transposed else ((bits + 1) * C, 2 * C)
    i, j = (lax.broadcasted_iota(jnp.int32, shape, d) for d in (0, 1))
    row, s = (j, i) if transposed else (i, j & (C - 1))
    shr, one = lax.shift_right_logical, jnp.int32(1)
    level, t = shr(row, jnp.int32(bits)), row & (C - 1)
    r = lax.shift_left(shr(t, level), level) \
        + shr(lax.shift_left(one, level), one)
    W = (s <= t).astype(F32) - ((level > 0) & (s <= r)).astype(F32)
    return W.astype(jnp.bfloat16)


def _split(x):
    """float32 ``x`` as three bfloat16 pieces, ``hi + mid + lo == x`` to
    the last bit: 3 x 8 significant bits hold float32's 24."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(F32)).astype(jnp.bfloat16)


def _signed_dot(W, x):
    """One pass of bfloat16 operands, accumulated in float32: exact for a
    ``W`` of 0 and +-1 and a piece of :func:`_split`."""
    return lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                           preferred_element_type=F32)


def _sums_fwd(g):
    return _sums(g), None


def _sums_bwd(_, d):
    Wt = _signed_sums(d[0].shape[0], transposed=True)
    return (sum(_signed_dot(Wt, piece)
                for piece in _split(jnp.concatenate(d, axis=0))),)


@_own_transpose(_sums_fwd, _sums_bwd)
def _sums(g):
    """The running sum ``G`` of ``g [C, K]`` and, a level ``m`` of the
    tree each, ``D_m = G - R_m``, ``R_m`` being ``G`` at the first token
    of the upper half of the row's block of ``2m``: one signed product
    (:func:`_signed_sums`) in two passes of the unit, ``hi`` and ``mid``
    stacked on the contraction, which ``C`` rows half fill.  ``D_m`` is
    exactly 0 on the row ``t = r``.  Its transpose is ``W^T dD``, three
    passes."""
    C = g.shape[0]
    W = _signed_sums(C)
    hi, mid, lo = _split(g)
    D = _signed_dot(W, jnp.concatenate([hi, mid], axis=0)) \
        + _signed_dot(W, jnp.concatenate([lo, jnp.zeros_like(lo)], axis=0))
    return tuple(D[i * C:(i + 1) * C] for i in range(len(_levels(C)) + 1))


def _diagonal(q, k):
    """``Diag(q k^T)``, the pairs ``s == t`` of ``B``: a row sum placed
    on the diagonal, no product, and none in its transpose."""
    t, s = _iotas(q.shape[0])
    return jnp.where(s == t, jnp.sum(q * k, axis=1, keepdims=True), 0.0)


_SUBLANES = 8      # rows of a float32 tile: slices by whole tiles are free


def _upper(x, m: int):
    """The rows of ``x [C, .]`` in the upper half of their block of
    ``2m``, ``[C / 2, .]``; all rows where ``m`` is not whole tiles."""
    if m % _SUBLANES:
        return x
    return jnp.concatenate([x[i + m:i + 2 * m]
                            for i in range(0, x.shape[0], 2 * m)], axis=0)


def _spread(y, m: int):
    """:func:`_upper` undone: the rows back in their places, zeros in the
    lower halves."""
    if m % _SUBLANES:
        return y
    zero = jnp.zeros((m,) + y.shape[1:], y.dtype)
    return jnp.concatenate([part for i in range(0, y.shape[0], m)
                            for part in (zero, y[i:i + m])], axis=0)


def _pairs_fwd(ku, qu, kl, mask, m):
    # one product for both: they share ``kl``; and of the rows only the
    # upper halves, which are all that ``mask`` keeps
    both = jnp.concatenate([_upper(ku, m), _upper(qu, m)], axis=0)
    P = _dot(both, kl, _NT)
    h = P.shape[0] // 2
    return (mask * _spread(P[:h], m), mask * _spread(P[h:], m)), \
        (both, kl, mask)


def _pairs_bwd(m, res, d):
    both, kl, mask = res
    D = jnp.concatenate([_upper(mask * d[0], m), _upper(mask * d[1], m)],
                        axis=0)
    dboth = _dot(D, kl)
    h = dboth.shape[0] // 2
    return (_spread(dboth[:h], m), _spread(dboth[h:], m),
            _dot(D, both, _TN), None)


@_own_transpose(_pairs_fwd, _pairs_bwd, static=(4,))
def _pairs(ku, qu, kl, mask, m):
    """Level ``m``'s part of ``A`` and of ``B``."""
    return _pairs_fwd(ku, qu, kl, mask, m)[0]


def _inverse_fwd(N):
    T = _inverse(N)
    return T, T


def _inverse_bwd(T, dT):
    # the inverse's own transpose: two products, not the tree's 24
    return (-_dot(_dot(T, dT, _TN), T, _NT),)


@_own_transpose(_inverse_fwd, _inverse_bwd)
def _inverse(N):
    """``(I + N)^-1`` for a strictly lower triangular ``N [C, C]``, by
    the tree of the module's text: twelve products, each waiting for the
    last.  A level's ``N * mask`` is 0 outside the upper halves' rows and
    ``T`` is still block diagonal there, so both products run on those
    rows alone."""
    t, s = _iotas(N.shape[0])
    T = (s == t).astype(F32)
    for m in _levels(N.shape[0]):
        X = _dot(_upper(N * _level_mask(t, s, m).astype(F32), m), T)
        T = T - _spread(_dot(_upper(T, m), _spread(X, m)), m)
    return T


def _decayed(e, S0):
    """``Diag(e) S0``: row ``c`` of ``S0 [K, V]`` scaled by ``e[0, c]``.
    The row ``e [1, K]`` becomes the column ``[K, 1]`` as ``Diag(e)``
    summed over its lanes, so that no product and no ``[1, K] -> [K, 1]``
    relayout is asked for, here or in the transpose."""
    K = e.shape[1]
    r, c = _iotas(K)
    column = jnp.sum(jnp.where(r == c, jnp.broadcast_to(e, (K, K)), 0.0),
                     axis=1, keepdims=True)
    return column * S0


def _chunk(S0, q, k, v, g, beta):
    """One chunk of one head on values, as the kernels run it: ``S0 [K,
    V]``; ``q, k, g [C, K]``; ``v [C, V]``; ``beta [C, 1]``; all float32.
    Returns ``(S1, O)``.  :func:`_chunk_step`'s arithmetic in the forms
    Mosaic lowers: two-dimensional products only, the running sum and its
    rebased differences from one signed product, the rows a level's
    mask keeps compacted where they are whole tiles."""
    C = q.shape[0]
    t, s = _iotas(C)
    G, *Ds = _sums(g)
    A = jnp.zeros((C, C), F32)
    Bm = _diagonal(q, k)
    for m, D in zip(_levels(C), Ds):
        # D = G - R, R being G at row (t // 2m) * 2m + m
        up = jnp.exp(jnp.minimum(D, 0.0))
        kl = k * jnp.exp(jnp.minimum(-D, 0.0))
        Am, Bmm = _pairs(k * up, q * up, kl,
                         _level_mask(t, s, m).astype(F32), m)
        A, Bm = A + Am, Bm + Bmm
    eG = jnp.exp(G)
    # k and q against the state in one product: they share ``S0``
    from_state = _dot(jnp.concatenate([k * eG, q * eG], axis=0), S0)
    U = _dot(_inverse(beta * A), beta * (v - from_state[:C]))
    O = from_state[C:] + _dot(Bm, U)
    Gc = G[C - 1:C, :]                              # [1, K]
    S1 = _decayed(jnp.exp(Gc), S0) + _dot(k * jnp.exp(Gc - G), U, _TN)
    return S1, O


def _column(beta_ref, h):
    """``beta`` arrives as the chunk's ``[C, H]`` rows; head ``h``'s
    column of it, ``[C, 1]``."""
    col = lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
    return jnp.sum(jnp.where(col == h, beta_ref[...].astype(F32), 0.0),
                   axis=1, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest):
    """``rest``: the output of the states, in the call that writes them,
    and the state's scratch."""
    from jax.experimental import pallas as pl

    *s0_refs, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _reset():
        state[...] = jnp.zeros_like(state)

    S0 = state[...]
    for s0_ref in s0_refs:
        s0_ref[...] = S0
    f = lambda ref: ref[...].astype(F32)
    state[...], O = _chunk(S0, f(q_ref), f(k_ref), f(v_ref), f(g_ref),
                           _column(beta_ref, pl.program_id(1)))
    o_ref[...] = O.astype(o_ref.dtype)


def _kda_forward(q, k, v, g, beta, *, chunk: int, interpret: bool,
                 states: bool = False):
    """``o [B, S, H, V]``; with ``states`` also the state each chunk
    starts from, ``[B, H, S / chunk, K, V]`` float32."""
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
    out_specs = [blk(V)]
    out_shape = [jax.ShapeDtypeStruct((B, S, H * V), v.dtype)]
    if states:
        out_specs.append(pl.BlockSpec((None, None, None, K, V),
                                      lambda b, h, n: (b, h, n, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, S // chunk, K, V), F32))
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(B, H, S // chunk),
        in_specs=[blk(K), blk(K), blk(V), blk(K),
                  pl.BlockSpec((None, chunk, H), lambda b, h, n: (b, n, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((K, V), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hvd_kda_fwd",
    )(flat(q), flat(k), flat(v), flat(g.astype(F32)), beta.astype(F32))
    o = out[0].reshape(B, S, H, V)
    return (o, out[1]) if states else o


# ---------------------------------------------------------------------------
# the reverse kernel
# ---------------------------------------------------------------------------

# The scoped VMEM a Mosaic kernel gets on the v5e unasked; the reverse
# kernel is compiled under it and carries the heads that fit.
_SCOPED_VMEM = 16 << 20


def _bwd_resident(heads: int, chunk: int, K: int, V: int,
                  itemsize: int) -> int:
    """VMEM bytes a grid step of the reverse kernel holds for ``heads``
    heads, in tiles of ``[chunk, max(K, V)]`` float32 padded to whole
    128-lane tiles: a head's blocks in two buffers each (``q, k, dq, dk``
    and ``v, dO, dv`` in the operands' type, ``g, dg`` and the state in
    float32), its state's cotangent and a tile more; and the 140 tiles
    one head's chunk keeps between its forward and its transpose, which
    the heads pass through one after another.  Checked against the
    compiler: ahead-of-time compiles for a v5e (jax 0.9.0, libtpu
    0.0.34; chunk 64, K = V = 128, bf16) need 4.6, 5.4, 6.4, 8.5 and
    12.8 MiB for 1, 2, 4, 8 and 16 heads (4.9, 5.5, 6.6, 8.9, 13.4
    here)."""
    lanes = lambda d: -(-d // 128) * 128
    k, v = lanes(K), lanes(V)
    tile = chunk * max(k, v, lanes(chunk)) * 4
    blocks = chunk * (4 * k + 3 * v) * itemsize + 2 * chunk * k * 4 \
        + K * v * 4
    return heads * (2 * blocks + K * v * 4 + tile) + 140 * tile


def _head_block(H: int, chunk: int, K: int, V: int, itemsize: int) -> int:
    """Heads a grid step of the reverse kernel carries: the most that
    divide ``H``, keep a block's columns whole lane tiles (or are all of
    ``H``) and fit :data:`_SCOPED_VMEM` by :func:`_bwd_resident` and half
    as much again; one if none does.  On the v5e at the published shape
    1 to 32 heads a step differed by under 1% when a head took 10 us (PR
    30; 6 us since PR 32) against a grid step's own 0.2, so the rule
    only has to stay inside the VMEM every kernel gets."""
    whole = lambda n: n == H or (n * K) % 128 == (n * V) % 128 == 0
    return max([n for n in range(1, H + 1) if H % n == 0 and whole(n)
                and _bwd_resident(n, chunk, K, V, itemsize) * 3 // 2
                <= _SCOPED_VMEM], default=1)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
                heads: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)                 # the last chunk
    def _reset():
        dstate[...] = jnp.zeros_like(dstate)

    K, V = s0_ref.shape[-2:]
    col = lax.broadcasted_iota(jnp.int32, dbeta_ref.shape, 1)
    first = pl.program_id(1) * heads                # of this block's heads

    def head(j, dbeta):
        ks = pl.ds(pl.multiple_of(j * K, K), K)
        vs = pl.ds(pl.multiple_of(j * V, V), V)
        f = lambda ref, sl: ref[:, sl].astype(F32)
        _, vjp = jax.vjp(
            _chunk, s0_ref[j], f(q_ref, ks), f(k_ref, ks), f(v_ref, vs),
            f(g_ref, ks), _column(beta_ref, first + j))
        dstate[j], dq, dk, dv, dg, db = vjp((dstate[j], f(do_ref, vs)))
        dq_ref[:, ks] = dq.astype(dq_ref.dtype)
        dk_ref[:, ks] = dk.astype(dk_ref.dtype)
        dv_ref[:, vs] = dv.astype(dv_ref.dtype)
        dg_ref[:, ks] = dg
        return jnp.where(col == j, db, dbeta)

    dbeta_ref[...] = lax.fori_loop(0, heads, head,
                                   jnp.zeros(dbeta_ref.shape, F32))


def _kda_backward(q, k, v, g, beta, states, do, *, chunk: int,
                  interpret: bool):
    """The five input cotangents from ``do`` and the states the forward
    wrote: the chunks walked last to first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, K = q.shape
    V = v.shape[-1]
    n = S // chunk
    hb = _head_block(H, chunk, K, V, q.dtype.itemsize)
    flat = lambda x: x.reshape(B, S, -1)
    blk = lambda X: pl.BlockSpec((None, chunk, hb * X),
                                 lambda b, h, i: (b, n - 1 - i, h))
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb),
        grid=(B, H // hb, n),
        in_specs=[blk(K), blk(K), blk(V), blk(K),
                  pl.BlockSpec((None, chunk, H),
                               lambda b, h, i: (b, n - 1 - i, 0)),
                  pl.BlockSpec((None, hb, None, K, V),
                               lambda b, h, i: (b, h, n - 1 - i, 0, 0)),
                  blk(V)],
        out_specs=[blk(K), blk(K), blk(V), blk(K),
                   # heads lie on a parallel axis: a block of beta's own
                   # [C, H] layout would be written by H / hb grid steps
                   pl.BlockSpec((None, None, chunk, hb),
                                lambda b, h, i: (b, h, n - 1 - i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * K), q.dtype),
                   jax.ShapeDtypeStruct((B, S, H * K), k.dtype),
                   jax.ShapeDtypeStruct((B, S, H * V), v.dtype),
                   jax.ShapeDtypeStruct((B, S, H * K), F32),
                   jax.ShapeDtypeStruct((B, H // hb, S, hb), F32)],
        scratch_shapes=[pltpu.VMEM((hb, K, V), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SCOPED_VMEM),
        interpret=interpret,
        name="hvd_kda_bwd",
    )(flat(q), flat(k), flat(v), flat(g.astype(F32)), beta.astype(F32),
      states, flat(do))
    shape = lambda x, like: x.reshape(like.shape).astype(like.dtype)
    dbeta = jnp.moveaxis(dbeta, 1, 2).reshape(beta.shape)
    return (shape(dq, q), shape(dk, k), shape(dv, v), shape(dg, g),
            dbeta.astype(beta.dtype))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _off_tpu() -> bool:
    """Where the kernels have to run interpreted (a test or a compile for
    a described chip patches this, as ``models.layers._flash_backend``)."""
    return jax.default_backend() != "tpu"


def _interpreted(interpret: Optional[bool]) -> bool:
    return _off_tpu() if interpret is None else interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def chunk_kda(q, k, v, g, beta, chunk: int = CHUNK,
              interpret: Optional[bool] = None):
    """``o`` of the recurrence above.  ``q, k, g [B, S, H, K]`` (``g`` the
    log decay), ``v [B, S, H, V]``, ``beta [B, S, H]`` -> ``[B, S, H, V]``
    in ``v``'s dtype.  ``interpret`` None: interpreted off the TPU."""
    return _kda_forward(q, k, v, g, beta, chunk=chunk,
                        interpret=_interpreted(interpret))


def _fwd_rule(q, k, v, g, beta, chunk, interpret):
    o, states = _kda_forward(q, k, v, g, beta, chunk=chunk, states=True,
                             interpret=_interpreted(interpret))
    return o, (q, k, v, g, beta, states)


def _bwd_rule(chunk, interpret, res, do):
    return _kda_backward(*res, do, chunk=chunk,
                         interpret=_interpreted(interpret))


chunk_kda.defvjp(_fwd_rule, _bwd_rule, optimize_remat=True)

