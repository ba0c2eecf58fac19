"""The routed experts of a decode tick in one Pallas kernel,
``hvd_moe_decode``.

A tick's rows (64 in the engine) make about 64 FLOPs of each byte of an
expert's three matrices, where a v5e does 240: reading the experts sets
the pace.  The tile loop of :func:`horovod_tpu.parallel.moe.moe_layer_held`
runs a touched expert as one tile, one XLA fusion after another, nothing
copying while a tile multiplies, and writes the tile's rows into a list
that four gathers read back (GLM-4.7-Flash: 384 tiles a tick read 7.25
GB at 67 to 73% of the bandwidth).  This kernel walks one layer's touched
experts in order instead: expert ``e + 1``'s gate, up and down matrices
(or its next block along ``F``) are copied into one of two VMEM slots
while expert ``e``'s products run, and every expert's output, weighted by
each row's routing weight for it (0 where the row did not pick it), is
added into one float32 ``[T, D]`` accumulator.  An expert that no row
took starts no copy; there is no list, no padding row and no gather.

The experts are the whole stack ``[L * E, D, F]`` / ``[L * E, F, D]`` in
HBM, addressed by row as ``hvd_paged_decode`` addresses pages, so no
layer's slice is copied out for the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32

#: Slots of expert blocks: one being multiplied, the next being copied
_SLOTS = 2

#: VMEM bytes the two slots of expert blocks may take: a whole expert of
#: GLM-4.7-Flash (2048 x 1536) is 18.9 MB, of Xing4.0 (3584 x 1024) 22 MB,
#: so both copy an expert whole; a wider one is copied in blocks along F
_SLOTS_BUDGET = 48 << 20


def block_f(D: int, F: int, itemsize: int) -> int:
    """Columns of ``F`` one copy takes: all of them while two slots of
    the three matrices fit :data:`_SLOTS_BUDGET`, else halved (whole
    128-lane tiles that divide ``F``) until they do or cannot be."""
    fb = F
    while _SLOTS * 3 * D * fb * itemsize > _SLOTS_BUDGET and fb % 256 == 0:
        fb //= 2
    return fb


def _moe_decode_kernel(rows_ref, n_ref, x_ref, ids_ref, wts_ref, gate_hbm,
                       up_hbm, down_hbm, o_ref, gbuf, ubuf, dbuf, acc, sem,
                       *, nf: int):
    """One call, one grid step: a loop over ``n * nf`` steps, step ``j``
    the block ``j % nf`` along F of the ``j // nf``-th touched expert,
    whose row in the stack is ``rows_ref[j // nf]``.  With ``S`` slots,
    step ``j + S - 1``'s three copies start before step ``j``'s are
    waited for; each wait takes exactly what its start sent (one
    semaphore a matrix and slot)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, fb = gbuf.shape[0], gbuf.shape[-1]
    steps = n_ref[0] * nf

    def copies(j, slot):
        row = rows_ref[j // nf]
        if nf == 1:
            src = (gate_hbm.at[row], up_hbm.at[row], down_hbm.at[row])
        else:
            cols = pl.ds(pl.multiple_of((j % nf) * fb, fb), fb)
            src = (gate_hbm.at[row, :, cols], up_hbm.at[row, :, cols],
                   down_hbm.at[row, cols])
        return [pltpu.make_async_copy(s, buf.at[slot], sem.at[m, slot])
                for m, (s, buf) in enumerate(zip(src, (gbuf, ubuf, dbuf)))]

    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    for ahead in range(S - 1):
        @pl.when(ahead < steps)
        def _first():
            for c in copies(ahead, ahead):
                c.start()

    def step(j, carry):
        slot = j % S

        @pl.when(j + S - 1 < steps)
        def _next():
            for c in copies(j + S - 1, (j + S - 1) % S):
                c.start()

        for c in copies(j, slot):
            c.wait()
        x = x_ref[...]
        w = jnp.sum(jnp.where(ids_ref[...] == rows_ref[j // nf],
                              wts_ref[...], 0.0), axis=1, keepdims=True)
        g = jnp.dot(x, gbuf[slot], preferred_element_type=F32)
        u = jnp.dot(x, ubuf[slot], preferred_element_type=F32)
        h = (g * jax.nn.sigmoid(g) * u).astype(dbuf.dtype)
        acc[...] += jnp.dot(h, dbuf[slot], preferred_element_type=F32) * w
        return carry

    lax.fori_loop(0, steps, step, 0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def moe_decode(tokens, experts, weights, counts, experts_held, first_row=0,
               *, interpret=False, fb=None):
    """Sum over each row's ``k`` picks of weight x SwiGLU_expert(row),
    through ``hvd_moe_decode``.

    ``tokens [T, D]``; ``experts [T, k]`` each pick's expert among the
    layer's ``E`` (0 to E - 1) and ``weights [T, k]`` its routing weight;
    ``counts [E]`` the rows each expert took (an expert of count 0 starts
    no copy); ``experts_held`` = ``{"gate", "up" [R, D, F], "down" [R, F,
    D]}``, the layer's experts from row ``first_row`` on (a traced scalar:
    a stack of several layers' experts is read where it lies).  Returns
    ``[T, D]`` in the tokens' type, summed in float32.  ``interpret``:
    False, True or a ``pltpu.InterpretParams``; ``fb`` the columns of
    ``F`` a copy takes (:func:`block_f` by default)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, D = tokens.shape
    E = counts.shape[0]
    gate, up, down = (experts_held[n] for n in ("gate", "up", "down"))
    F, dtype = gate.shape[-1], gate.dtype
    fb = fb or block_f(D, F, dtype.itemsize)
    assert F % fb == 0, (F, fb)
    first_row = jnp.asarray(first_row, jnp.int32)
    touched = counts > 0
    rows = jnp.nonzero(touched, size=E, fill_value=0)[0].astype(jnp.int32)
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, r, n: (0,) * len(
        shape))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    vmem = _SLOTS * 3 * D * fb * dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole(T, D), whole(*experts.shape),
                  whole(*weights.shape), any_, any_, any_],
        out_specs=whole(T, D),
        scratch_shapes=[pltpu.VMEM((_SLOTS, D, fb), dtype),
                        pltpu.VMEM((_SLOTS, D, fb), dtype),
                        pltpu.VMEM((_SLOTS, fb, D), dtype),
                        pltpu.VMEM((T, D), F32),
                        pltpu.SemaphoreType.DMA((3, _SLOTS))],
    )
    return pl.pallas_call(
        functools.partial(_moe_decode_kernel, nf=F // fb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), tokens.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
        name="hvd_moe_decode",
    )(rows + first_row, jnp.sum(touched, dtype=jnp.int32).reshape(1),
      tokens.astype(dtype), experts.astype(jnp.int32) + first_row,
      weights.astype(F32), gate, up, down)
