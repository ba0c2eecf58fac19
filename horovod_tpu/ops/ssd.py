"""The state-space scan of a Mamba-2 mixer (SSD), for the serving steps.

Head ``h`` of ``H`` reads group ``h // (H / G)`` of the ``G`` groups of
``B`` and ``C``; its state is ``S [N, P]`` (``N`` the state size, ``P``
the head's width), kept ``n`` before ``p`` so that ``p`` lies along the
chip's 128 lanes::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t B_t x_t^T
    y_t = S_t^T C_t

``dt`` is already through its softplus; a ``dt`` of 0 leaves the state
as it was (``exp(0) = 1`` and the update term is 0), which is how a
bucketed prompt's padding is kept out of the state.  ``D`` (the skip
term) and everything around the scan are the model's.

Three forms of the one recurrence:

- :func:`recurrent_ssd`, token by token: the tests' yardstick;
- :func:`ssd_chunked`, the prompt's: chunks of ``chunk`` tokens, inside a
  chunk the quadratic (attention-like) form, across chunks the state
  carried; XLA, float32 at ``highest`` precision (its FLOPs are under 1%
  of a prompt's);
- :func:`ssd_decode`, a decode tick's: the Pallas kernel
  ``hvd_ssd_decode``, one grid step a row, which addresses one layer of
  the WHOLE slot array ``[L, R, H, N, P]`` and writes the row's state back
  in place, as ``hvd_paged_decode`` addresses pages inside the pool; a
  row without a stream starts no copy.  :func:`ssd_step` is the same tick
  in ``jax.numpy`` on one layer's slots, for the XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _heads_by_group(H: int, G: int):
    if H % G:
        raise ValueError(f"{G} groups of B and C must divide {H} heads")
    return jnp.arange(H) // (H // G)


def recurrent_ssd(x, dt, A, B, C, state=None):
    """The recurrence a token at a time.  ``x [b, T, H, P]``, ``dt [b, T,
    H]``, ``A [H]`` (negative), ``B, C [b, T, G, N]``, ``state [b, H, N,
    P]`` or None (zeros).  Returns ``(y [b, T, H, P], state)``, float32."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    hg = _heads_by_group(H, B.shape[2])
    S0 = jnp.zeros((b, H, N, P), F32) if state is None else state.astype(F32)

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        decay = jnp.exp(dt_t * A)[..., None, None]
        S = S * decay + B_t[:, hg, :, None] * (dt_t[..., None] * x_t)[
            :, :, None, :]
        return S, jnp.einsum("bhnp,bhn->bhp", S, C_t[:, hg], precision=_HI)

    seq = lambda a: jnp.swapaxes(a.astype(F32), 0, 1)
    S, y = lax.scan(step, S0, (seq(x), seq(dt), seq(B), seq(C)))
    return jnp.swapaxes(y, 0, 1), S


def ssd_chunked(x, dt, A, B, C, chunk: int = 128, state=None):
    """:func:`recurrent_ssd`'s result, a chunk at a time.  Inside a chunk
    of ``L`` tokens the output is ``y_i = sum_{j <= i} (C_i . B_j)
    exp(sum_{j < s <= i} dt_s A) dt_j x_j`` (an ``[L, L]`` product a
    head), plus what the state the chunk starts from gives; across
    chunks the state is carried by a scan.  ``T`` need not be a multiple
    of ``chunk``: the tail is padded with ``dt = 0``."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    K = H // G                                   # heads a group
    pad = (-T) % chunk
    if pad:
        padt = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = padt(x), padt(dt), padt(B), padt(C)
    n = (T + pad) // chunk
    # [b, n, L, G, K, ...]: a chunk's tokens, heads split by their group
    x = x.astype(F32).reshape(b, n, chunk, G, K, P)
    dt = dt.astype(F32).reshape(b, n, chunk, G, K)
    B = B.astype(F32).reshape(b, n, chunk, G, N)
    C = C.astype(F32).reshape(b, n, chunk, G, N)
    cum = jnp.cumsum(dt * A.reshape(G, K), axis=2)          # [b,n,L,G,K]
    xdt = x * dt[..., None]

    # within a chunk: decay from token j to token i (i >= j)
    seg = cum[:, :, :, None] - cum[:, :, None, :]          # [b,n,i,j,G,K]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    CB = jnp.einsum("bcign,bcjgn->bcijg", C, B, precision=_HI)
    y = jnp.einsum("bcijg,bcijgk,bcjgkp->bcigkp", CB, decay, xdt,
                   precision=_HI)

    # what each chunk adds to the state at its end, then across chunks
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # [b,n,L,G,K]
    adds = jnp.einsum("bcjgn,bcjgk,bcjgkp->bcgknp", B, to_end, xdt,
                      precision=_HI)
    S0 = jnp.zeros((b, G, K, N, P), F32) if state is None else \
        state.astype(F32).reshape(b, G, K, N, P)

    def carry(S, inp):
        add, d = inp
        return S * d[..., None, None] + add, S               # S: the start

    S, starts = lax.scan(carry, S0, (jnp.swapaxes(adds, 0, 1),
                                     jnp.swapaxes(jnp.exp(cum[:, :, -1]),
                                                  0, 1)))
    starts = jnp.swapaxes(starts, 0, 1)                     # [b,n,G,K,N,P]
    y = y + jnp.einsum("bcign,bcgknp->bcigkp", C, starts,
                       precision=_HI) * jnp.exp(cum)[..., None]
    y = y.reshape(b, n * chunk, H, P)[:, :T]
    return y, S.reshape(b, H, N, P)


def ssd_step(state, xdt, decay, B, C):
    """One tick on one layer's slots, in ``jax.numpy``: ``state [R, H, N,
    P]``, ``xdt = dt x [R, H, P]``, ``decay = exp(dt A) [R, H]``, ``B, C
    [R, G, N]`` -> ``(y [R, H, P], state)``, float32."""
    hg = _heads_by_group(state.shape[1], B.shape[1])
    S = state.astype(F32) * decay[..., None, None] + \
        B[:, hg, :, None] * xdt[:, :, None, :]
    return jnp.einsum("rhnp,rhn->rhp", S, C[:, hg], precision=_HI), S


# ---------------------------------------------------------------------------
# the decode tick's kernel
# ---------------------------------------------------------------------------

def unit_heads(H: int, G: int) -> int:
    """Heads the kernel copies and updates at once: the most, up to 8
    (a unit of ``[8, 256, 128]`` in bf16 is 512 KiB), that lie in one
    group of ``B`` and ``C`` and leave an even count of units a row (the
    next row's first unit goes to slot 0 while the last unit is in slot
    1)."""
    for hu in (8, 4, 2, 1):
        if (H // G) % hu == 0 and (H // hu) % 2 == 0:
            return hu
    raise ValueError(f"no unit of heads fits {H} heads in {G} groups")


def _ssd_decode_kernel(layer_ref, live_ref, xdt_ref, decay_ref, b_ref,
                       c_ref, state_in, y_ref, state_out, inbuf, outbuf,
                       sem, pending, *, group_heads: int):
    """One row of a tick: its state comes in a unit of heads at a time
    (``make_async_copy`` into one of two VMEM slots, the next unit
    started before this one is computed, a row's last unit starting the
    first of the next row that has a stream), is updated in float32,
    written back to the slot array it came from from one of two out
    slots, whose copies are waited for only when the slot is needed again
    and at the call's last step.  A row whose ``live`` flag is 0 starts
    no copy and reads as zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    R = pl.num_programs(0)
    li = layer_ref[0]
    HU = inbuf.shape[1]
    U = xdt_ref.shape[0] // HU                   # units a row (even)

    def copy_in(row, u, slot):
        return pltpu.make_async_copy(state_in.at[li, row, pl.ds(u * HU, HU)],
                                     inbuf.at[slot], sem.at[0, slot])

    def copy_out(row, u, slot):
        return pltpu.make_async_copy(outbuf.at[slot],
                                     state_out.at[li, row, pl.ds(u * HU, HU)],
                                     sem.at[1, slot])

    def next_live(row):
        """The first row from ``row`` on with a stream, else ``R``."""
        return lax.while_loop(
            lambda r: (r < R) & (live_ref[jnp.minimum(r, R - 1)] == 0),
            lambda r: r + 1, row)

    @pl.when(b == 0)
    def _first():
        pending[0] = 0
        pending[1] = 0
        head = next_live(0)
        pl.when(head < R)(lambda: copy_in(head, 0, 0).start())

    @pl.when(live_ref[b] != 0)
    def _row():
        for u in range(U):
            slot = u % 2
            if u + 1 < U:
                copy_in(b, u + 1, 1 - slot).start()
            else:
                nxt = next_live(b + 1)
                pl.when(nxt < R)(lambda: copy_in(nxt, 0, 0).start())
            copy_in(b, u, slot).wait()
            hs = pl.ds(u * HU, HU)
            g = u * HU // group_heads
            S = inbuf[slot].astype(F32) * decay_ref[hs] + \
                b_ref[g] * xdt_ref[hs]                  # [HU, N, P]
            y_ref[hs] = jnp.sum(S * c_ref[g], axis=1, keepdims=True)

            @pl.when(pending[slot] != 0)
            def _free():
                copy_out(b, u, slot).wait()

            outbuf[slot] = S.astype(outbuf.dtype)
            copy_out(b, u, slot).start()
            pending[slot] = 1

    @pl.when(live_ref[b] == 0)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(b == R - 1)
    def _last():
        for slot in (0, 1):
            @pl.when(pending[slot] != 0)
            def _drain():
                copy_out(0, 0, slot).wait()


def ssd_decode(state, layer, live, xdt, decay, B, C, *,
               interpret=False):
    """One decode tick of every row's state in layer ``layer`` of the
    whole slot array ``state [L, R, H, N, P]``, in place (donate it
    through the step): ``live [R]`` nonzero where a row holds a stream;
    ``xdt = dt x [R, H, P]``, ``decay = exp(dt A) [R, H]``, ``B, C [R, G,
    N]``.  Returns ``(y [R, H, P] float32, state)``: ``y`` without the
    ``D`` term, zeros on a row without a stream, whose state is left as
    it was.  ``interpret``: False, True or a ``pltpu.InterpretParams``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, R, H, N, P = state.shape
    G = B.shape[1]
    HU = unit_heads(H, G)
    row = lambda *blk: pl.BlockSpec((None,) + blk,
                                    lambda r, li, lv: (r,) + (0,) * len(blk))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[row(H, 1, P), row(H, 1, 1), row(G, N, 1), row(G, N, 1),
                  any_],
        out_specs=[row(H, 1, P), any_],
        scratch_shapes=[pltpu.VMEM((2, HU, N, P), state.dtype),
                        pltpu.VMEM((2, HU, N, P), state.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    y, state = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, group_heads=H // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, H, 1, P), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: layer, live, xdt, decay, B, C, state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hvd_ssd_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.astype(jnp.int32),
      xdt.astype(F32)[:, :, None, :], decay.astype(F32)[:, :, None, None],
      B.astype(F32)[..., None], C.astype(F32)[..., None], state)
    return y[:, :, 0], state
