"""Expert parallelism: Switch-style MoE with all_to_all dispatch.

ABSENT as a strategy in the reference, but its ``hvd.alltoall`` verb
(† ``message.h RequestType::ALLTOALL``, ``MPI_Alltoallv``) exists precisely
for this exchange pattern (DLRM embedding swaps, MoE token dispatch) —
BASELINE config 5 makes it a required capability.

Design (Switch Transformer, arXiv:2101.03961, re-expressed for TPU):
top-1 routing with static capacity so every shape is fixed at trace time
(XLA requirement — no dynamic gathers), dispatch/combine as einsums with
one-hot masks (MXU-friendly), and the token exchange as a single
``all_to_all`` over the ``ep`` axis in each direction.  Overflowed tokens
are dropped (standard capacity semantics) and recovered by the residual
connection in the caller.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import REGISTRY as _obs
from ..obs.trace import region
from ..ops.moe_decode import moe_decode

_m_dropped = _obs.counter(
    "hvd_moe_dropped_tokens_total",
    "tokens dropped past expert capacity (the capacity-factor tuning "
    "signal: a persistently nonzero rate means the factor is too low "
    "for the observed routing skew)", ("layer",))


def record_dropped_tokens(count, layer: str = "0") -> None:
    """Count capacity overflow drops into the per-layer counter.

    Host-side (counters are process state, not traced values): callers
    inside jit return the drop count as an output and record it here
    after the step.
    """
    c = float(count)
    if c > 0:
        _m_dropped.labels(layer=str(layer)).inc(c)


def switch_route(router_logits: jax.Array, capacity: int
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-1 routing masks.

    router_logits: [T, E].  Returns (dispatch [T, E, C] float, combine
    [T, E, C] float, aux_loss scalar, dropped [T] bool).

    ``dropped`` marks tokens past their expert's capacity explicitly —
    they contribute nothing to dispatch/combine (the residual recovers
    them), but silent drops made capacity-factor tuning blind; callers
    feed ``dropped.sum()`` to :func:`record_dropped_tokens`.
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                  # [T]
    expert_onehot = jax.nn.one_hot(expert_idx, E)            # [T, E]
    # Load-balancing auxiliary loss († Switch eq. 4).
    density = expert_onehot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux_loss = E * jnp.sum(density * density_proxy)
    # Position of each token within its expert's capacity buffer.
    position = (jnp.cumsum(expert_onehot, axis=0) - 1.0) * expert_onehot
    keep = (position < capacity) & (expert_onehot > 0)       # [T, E]
    pos_onehot = jax.nn.one_hot(position.astype(jnp.int32), capacity)
    dispatch = keep[..., None] * pos_onehot                  # [T, E, C]
    gate = (probs * expert_onehot).sum(axis=-1)              # [T]
    combine = dispatch * gate[:, None, None]
    dropped = ~keep.any(axis=-1)                             # [T]
    return dispatch.astype(router_logits.dtype), combine, aux_loss, dropped


def moe_layer_local(tokens: jax.Array,
                    router_kernel: jax.Array,
                    expert_fn: Callable[[Any, jax.Array], jax.Array],
                    expert_params: Any, *,
                    axis_name: str = "ep",
                    capacity_factor: float = 1.25,
                    buffer_constraint: Callable[[jax.Array], jax.Array]
                    = lambda x: x,
                    return_drops: bool = False,
                    ):
    """MoE layer inside a mapped context.

    tokens: local [T, D]; router_kernel: [D, E_total] replicated;
    expert_params: this device's experts, leaves [E_local, ...].
    Returns (output [T, D], aux_loss scalar); with ``return_drops``,
    (output, aux_loss, dropped-token count scalar) — the count is a
    traced value, so jitted callers thread it out and feed
    :func:`record_dropped_tokens` host-side.

    ``buffer_constraint`` pins the expert buffers' sharding on the mesh
    axes that stay automatic inside the caller's ``shard_map`` (the token
    dim is reduced away building them, so they should be replicated over
    dp/fsdp) — without it GSPMD's propagator smears batch shardings onto
    the expert dim of the saved-for-backward buffers and pays an
    involuntary full rematerialization each layer.
    """
    n = axis_size(axis_name)
    T, D = tokens.shape
    E_total = router_kernel.shape[1]
    if E_total % n:
        raise ValueError(f"experts ({E_total}) must divide ep size ({n})")
    E_local = E_total // n
    capacity = max(1, int(T * capacity_factor / E_total))

    with region("moe.route"):
        logits = tokens @ router_kernel                       # [T, E]
        dispatch, combine, aux, dropped = switch_route(logits, capacity)

    with region("moe.experts"):
        # Gather tokens into expert buffers: [E, C, D].
        expert_inputs = buffer_constraint(
            jnp.einsum("tec,td->ecd", dispatch, tokens))
        # Exchange: send each expert's buffer to its owner device.
        # [E, C, D] -> [n, E_local, C, D] -> a2a -> [n, E_local, C, D]
        # where the leading dim now indexes source rank.
        shaped = expert_inputs.reshape(n, E_local, capacity, D)
        received = lax.all_to_all(shaped, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
        # received: [n, E_local, C, D] — tokens from every rank for my
        # experts.
        per_expert = buffer_constraint(received.transpose(1, 0, 2, 3).reshape(
            E_local, n * capacity, D))
        expert_out = buffer_constraint(jax.vmap(expert_fn)(
            expert_params, per_expert))                 # [E_local, n*C, D]
        # Route back: inverse exchange.
        back = expert_out.reshape(E_local, n, capacity, D).transpose(
            1, 0, 2, 3)
        returned = lax.all_to_all(back, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
    with region("moe.combine"):
        # returned: [n(expert-owner), E_local, C, D] == my tokens' results.
        results = buffer_constraint(returned.reshape(E_total, capacity, D))
        out = jnp.einsum("tec,ecd->td", combine, results)
    if return_drops:
        return (out.astype(tokens.dtype), aux,
                jnp.sum(dropped.astype(jnp.float32)))
    return out.astype(tokens.dtype), aux


def moe_layer(tokens: jax.Array, router_kernel: jax.Array,
              expert_fn: Callable[[Any, jax.Array], jax.Array],
              stacked_expert_params: Any, mesh: Mesh, *,
              axis_name: str = "ep",
              capacity_factor: float = 1.25,
              layer: str = "0") -> tuple[jax.Array, jax.Array]:
    """Standalone entry: tokens [T, D] sharded over ``axis_name`` on dim 0;
    expert params leaves [E_total, ...] sharded over ``axis_name``.

    Capacity overflow drops are counted into
    ``hvd_moe_dropped_tokens_total{layer}`` after the step (the count
    rides out of the jitted region as an output)."""

    def local(tok, rk, params):
        out, aux, drops = moe_layer_local(
            tok, rk, expert_fn,
            jax.tree.map(lambda a: a, params),
            axis_name=axis_name, capacity_factor=capacity_factor,
            return_drops=True)
        return out, lax.pmean(aux, axis_name), lax.psum(drops, axis_name)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P(), P(axis_name)),
        out_specs=(P(axis_name), P(), P()),
        check_vma=False)
    out, aux, drops = jax.jit(fn)(tokens, router_kernel,
                                  stacked_expert_params)
    record_dropped_tokens(jax.device_get(drops), layer)
    return out, aux


def _softmax_np(x):
    import numpy as np
    x = x - x.max(axis=-1, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=-1, keepdims=True)


def moe_layer_hvd(tokens, router_kernel, expert_fn, expert_params, *,
                  capacity_factor: float = 1.25, layer: str = "0"):
    """Expert parallelism over the engine's negotiated ``hvd.alltoall``
    — the 4th collective verb at job scale.

    Where :func:`moe_layer` is the in-jit path (static capacity buffers,
    ``lax.all_to_all`` inside one compiled program), this is the
    process-level eager path: routing happens host-side, per-expert
    counts are exchanged FIRST (a tiny uniform alltoall), so the token
    exchange itself ships only the kept rows — the alltoallv form with
    split sizes known on every rank, no padded capacity slots on the
    wire.  Multi-process correct: the same code runs in the
    single-controller rig (one process driving n ranks) and under
    ``hvdrun`` (one rank per process).

    Args: ``tokens`` — list of per-rank [T_k, D] arrays, one entry per
    rank this process drives; ``router_kernel`` [D, E_total] replicated;
    ``expert_params`` — list of per-rank pytrees, leaves [E_local, ...]
    (rank r owns experts ``r*E_local .. (r+1)*E_local-1``).

    Returns ``(outs, aux, dropped)``: per-rank outputs [T_k, D], the
    mean Switch aux loss over local ranks, and the total overflow drops
    (also counted into ``hvd_moe_dropped_tokens_total{layer}``).
    """
    import numpy as np
    import horovod_tpu as hvd

    n = hvd.size()
    toks = [np.asarray(t, np.float32) for t in tokens]
    rk = np.asarray(router_kernel, np.float32)
    local = len(toks)
    E_total = rk.shape[1]
    if E_total % n:
        raise ValueError(f"experts ({E_total}) must divide world ({n})")
    E_local = E_total // n

    counts = np.zeros((local, E_total), np.int32)   # kept per expert
    send_orders, sends, gates, dropped, auxes = [], [], [], 0, []
    for k, tok in enumerate(toks):
        T = tok.shape[0]
        capacity = max(1, int(T * capacity_factor / E_total))
        probs = _softmax_np(tok @ rk)
        eidx = probs.argmax(axis=-1)
        gate = probs[np.arange(T), eidx]
        onehot = np.eye(E_total, dtype=np.float32)[eidx]
        auxes.append(float(
            E_total * (onehot.mean(0) * probs.mean(0)).sum()))
        pos = np.empty(T, np.int64)
        for e in range(E_total):
            sel = eidx == e
            pos[sel] = np.arange(int(sel.sum()))
            counts[k, e] = min(int(sel.sum()), capacity)
        keep = pos < capacity
        dropped += int((~keep).sum())
        kept = np.nonzero(keep)[0]
        order = kept[np.argsort(eidx[kept], kind="stable")]
        send_orders.append(order)
        sends.append(tok[order])
        gates.append(gate)

    # (1) per-expert counts first — destination j learns exactly how many
    # rows each source sends for each of its experts, so every split size
    # below is known before any token moves.
    splits_cnt = np.full((local, n), E_local, np.int32)
    cnt_recv = hvd.alltoall([c for c in counts], splits=splits_cnt)
    # cnt_recv[k]: [n*E_local] — source-major counts for rank k's experts.
    # (2) kept tokens, expert-ascending per destination block.
    splits = np.stack([counts[k].reshape(n, E_local).sum(axis=1)
                       for k in range(local)]).astype(np.int32)
    data_recv = hvd.alltoall(sends, splits=splits)

    # (3) run the local experts on expert-major regroupings.
    results = []
    for k in range(local):
        cnt = np.asarray(cnt_recv[k]).reshape(n, E_local)
        block = np.asarray(data_recv[k])          # source-major rows
        src_off = np.concatenate([[0], cnt.sum(axis=1).cumsum()])
        within = np.concatenate(
            [np.zeros((n, 1), np.int64), cnt.cumsum(axis=1)], axis=1)
        out_rows = np.zeros_like(block)
        params = expert_params[min(k, len(expert_params) - 1)]
        for e in range(E_local):
            rows = [block[src_off[i] + within[i, e]:
                          src_off[i] + within[i, e + 1]] for i in range(n)]
            x_e = np.concatenate(rows, axis=0) if cnt[:, e].sum() else None
            if x_e is None or not len(x_e):
                continue
            p_e = jax.tree.map(lambda a: jnp.asarray(a)[e], params)
            y_e = np.asarray(expert_fn(p_e, jnp.asarray(x_e)))
            off = 0
            for i in range(n):
                m = int(cnt[i, e])
                out_rows[src_off[i] + within[i, e]:
                         src_off[i] + within[i, e + 1]] = y_e[off:off + m]
                off += m
        results.append(out_rows)

    # (4) inverse exchange: each destination returns exactly the rows it
    # received, so the transposed split matrix routes them home.
    splits_back = np.stack([np.asarray(cnt_recv[k]).reshape(
        n, E_local).sum(axis=1) for k in range(local)]).astype(np.int32)
    back = hvd.alltoall(results, splits=splits_back)

    outs = []
    for k, tok in enumerate(toks):
        out = np.zeros_like(tok)
        rows = np.asarray(back[k])   # dest-major == my original send order
        order = send_orders[k]
        out[order] = gates[k][order, None] * rows
        outs.append(out)
    record_dropped_tokens(dropped, layer)
    return outs, float(np.mean(auxes)) if auxes else 0.0, dropped


# ---------------------------------------------------------------------------
# dropless top-k routing over the experts held here
# ---------------------------------------------------------------------------

_m_held = _obs.counter(
    "hvd_moe_held_pairs_total",
    "(token, expert) pairs routed to an expert this chip holds, summed "
    "over steps (the work of its grouped products: over tokens x k it is "
    "the share of the routed traffic that lands here)", ("layer",))
_m_load = _obs.gauge(
    "hvd_moe_expert_load_max_over_mean",
    "pairs of the fullest held expert over the mean of the held experts "
    "in the last recorded step (1.0 = even; the straggler that an "
    "expert-parallel step waits for)", ("layer",))


_m_combine = _obs.counter(
    "hvd_moe_combine_steps_total",
    "recorded steps of the dropless expert layer by the form in which its "
    "grouped products' results got back to the tokens' rows (list: every "
    "scored expert is held here; add: a share of them; kernel: every one "
    "is held and the step is a decode tick through hvd_moe_decode)",
    ("layer", "form"))


def record_held_pairs(expert_counts, layer: str = "0", *,
                      scored: int, kernel: bool = False) -> None:
    """Count one step's routed load into the per-layer metrics.

    ``expert_counts``: pairs per held expert, ``[E_held]``; ``scored``:
    how many experts the layer's router scores and ``kernel`` whether the
    step was a tick that ran its kernels, by which the step is counted
    under its :func:`combine_form`.  Host-side, after the step, as
    :func:`record_dropped_tokens`."""
    import numpy as np
    c = np.asarray(expert_counts, np.float64)
    _m_held.labels(layer=str(layer)).inc(float(c.sum()))
    if c.sum() > 0:
        _m_load.labels(layer=str(layer)).set(float(c.max() / c.mean()))
    _m_combine.labels(layer=str(layer),
                      form=combine_form(c.size, scored, kernel)).inc()


def topk_route(scores: jax.Array, bias: jax.Array, k: int, *,
               renormalize: bool = True, scale: float = 1.0
               ) -> tuple[jax.Array, jax.Array]:
    """Top-k routing with a selection bias (DeepSeek-V3 style,
    arXiv:2412.19437 section 2.1.2).

    ``scores [T, E]`` are the gate's activations (sigmoid or softmax
    already applied), ``bias [E]`` moves the selection only: the ``k``
    experts a token takes are the top k of ``scores + bias``; their
    weights are ``scores`` at those experts, divided by their sum under
    ``renormalize``, times ``scale``.  Returns ``(experts [T, k] int32,
    weights [T, k])``, best first, ties to the lower index, as
    ``lax.top_k`` gives them; the weights carry the gradient, the choice
    none.

    The choice is ``k`` passes of first-maximum-and-mask over ``[T, E]``
    and a weight the masked sum of its pass, dense work of the vector
    unit: at 32,768 tokens of 256 scores on a v5e the eight passes take
    0.2 ms and the weights 0.04, where ``lax.top_k``, a stable sort of
    every token's scores with an iota beside them, took 0.96 and
    ``take_along_axis``, a gather of ``T x k`` scalars one at a time,
    2.7; the gather's transpose is a scatter-add into ``[T, E]``, the
    sum's a select.
    """
    choose = lax.stop_gradient(scores + bias)
    column = lax.broadcasted_iota(jnp.int32, choose.shape, 1)
    experts, weights = [], []
    for _ in range(k):
        best = jnp.argmax(choose, axis=-1).astype(jnp.int32)
        taken = column == best[:, None]
        experts.append(best)
        weights.append(jnp.sum(jnp.where(taken, scores, 0), axis=-1))
        choose = jnp.where(taken, -jnp.inf, choose)
    experts, weights = jnp.stack(experts, -1), jnp.stack(weights, -1)
    if renormalize:
        # The barrier keeps the stack an array and the sum a reduction
        # over its k: XLA otherwise turns the sum of a concatenation into
        # a chain of adds, which rounds otherwise (v5e: with it the
        # weights are bit for bit what the gather's were, without it a
        # last bit here and there, and the step's rounding noise with it).
        weights = lax.optimization_barrier(weights)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * scale


def _swiglu_tile(x, wg, wu, wd, wt):
    """One tile's rows through one expert, weighted: ``[tile, D]``."""
    hidden = jax.nn.silu(x @ wg) * (x @ wu)
    return (hidden @ wd) * wt[:, None].astype(x.dtype)


def _padded_lists(place, weights):
    """The loop's lists from one sort.  ``place [L]`` gives every entry
    its row among the rows of all tiles: the ``T * k`` pairs first, in
    pair order, then ``L - T * k`` fillers, of which an expert's run
    takes as many as its last tile lacks; a pair held elsewhere and a
    filler not needed have ``L``, past every tile in use.  Sorted by
    ``place`` the entries are the tiles' rows as they lie, and the pair's
    own index (``T * k`` for a filler) and weight (0) ride along as the
    sort's operands.  Returns each row's token (``T`` for padding), pair
    and weight, ``[L]`` each."""
    with region("moe.route"):
        n, fill = weights.size, place.shape[0] - weights.size
        pairs = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                                 jnp.full((fill,), n, jnp.int32)])
        w = jnp.concatenate([weights.reshape(-1),
                             jnp.zeros((fill,), weights.dtype)])
        _, pairs, w = lax.sort((place, pairs, w), num_keys=1,
                               is_stable=False)
        return pairs // weights.shape[1], pairs, w


def _tile_operands(i, tile, tokens, rows, weights, tile_expert, experts):
    idx = lax.dynamic_slice(rows, (i * tile,), (tile,))
    wt = lax.dynamic_slice(weights, (i * tile,), (tile,))
    # a padding row names token T: it reads as zeros and is dropped
    # on the way back
    x = jnp.take(tokens, idx, axis=0, mode="fill", fill_value=0)
    w = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
        a, tile_expert[i], 0, keepdims=False), experts)
    return idx, x, wt, w


def combine_form(held: int, scored: int, kernel: bool = False) -> str:
    """How :func:`_grouped_experts` gets its experts' results back to the
    tokens' rows, from what the layer's caller holds and runs:
    ``"list"`` where it holds every expert the router scores (each
    token's ``k`` pairs are all computed here, so the padded list is as
    full as routing makes it), ``"add"`` where it holds a share (the
    list's static bound is mostly bound: 16 of 256 experts fill a
    twentieth of it), and ``"kernel"`` where it holds every one and
    ``kernel`` says the step is a decode tick (one position a row) whose
    caller runs its kernels: ``hvd_moe_decode`` walks the touched experts
    and sums the rows' results itself, no tile and no list.  The one
    rule, for the program and for what the host says of it
    (``moe_combine`` on the serving spans,
    ``hvd_moe_combine_steps_total``)."""
    if held != scored:
        return "add"
    return "kernel" if kernel else "list"


@partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _grouped_experts(tokens, weights, place, tile_expert, n_tiles, experts,
                     pair_slot, tick, tile: int, interpret):
    """Sum over the held (row, expert) pairs of weight x SwiGLU_expert.

    The pairs lie sorted by expert, each expert's run padded to whole
    tiles of ``tile`` rows, so a tile belongs to one expert: ``weights
    [T, k]`` are the pairs' routing weights in pair order, ``place`` each
    pair's row among the rows of all tiles and the padding's
    (:func:`_padded_lists`: one sort, the only pass over the pairs that
    is not dense vector work, yields each row's pair and weight),
    ``tile_expert [M / tile]`` each tile's expert and ``n_tiles`` how
    many tiles are in use.  A loop over the tiles in use gathers a tile's
    rows and runs them through that expert's three matrices: the work is
    that of the pairs held (to a tile), whatever the static bound ``M``.
    Nothing moves the ``T * k`` pairs one scalar at a time: on a v5e a
    scatter or gather of 262,144 scalars takes 1.2 to 2.7 ms (the two
    lists were two such scatters behind two such gathers), the sort of
    270,336 keys with both operands 0.39.  The results reach the tokens'
    rows in one of three forms (:func:`combine_form` picks; the backward is
    the same):

    - ``pair_slot`` is ``None``, the add form: every tile adds its rows
      into a carried ``[T, D]`` at the places routing chose, a
      scatter-add of ``tile`` rows a tile, one rounding to the tokens'
      type a pair.  No buffer of ``M`` rows of activations exists.
    - ``pair_slot [T, k]`` names the slot of each of a token's pairs
      (``M - 1`` for a pair held elsewhere), the list form: every tile
      writes its rows where it lies in a carried ``[M, D]`` list, a
      contiguous block, and after the loop every token gathers its ``k``
      rows, one column of ``pair_slot`` at a time, and sums them in
      float32, rounded once.  It costs the list: ``M x D`` of the
      tokens' type, uninitialised but for its last tile, which no tile
      in use reaches (the bound keeps ``E_held`` rows over what routing
      can fill): zeros for the pairs that are not here.  Every other row
      a token reads, a tile has written.
    - ``tick = (choice [T, k], counts [E_held], first_row)`` besides, the
      kernel form (a decode tick): no tile runs, ``hvd_moe_decode``
      (:func:`~horovod_tpu.ops.moe_decode.moe_decode`, ``interpret`` its
      own) walks the experts of nonzero count, each pair's expert
      ``choice`` among the held and its weight, into a float32 ``[T,
      D]``.  Only the undifferentiated call takes it: under a gradient
      the forward rule runs the list form, whose residuals the backward
      reads, so a differentiated step keeps the tile loop.

    The backward walks the same tiles; a tile's weight gradients go
    straight to their pairs' places in ``[T, k]``, ``tile`` scalars a
    tile in use (3 us at 512), and not through the sort's transpose,
    which would gather and scatter all ``T * k``."""
    if tick is not None:
        with region("moe.experts"):
            return moe_decode(tokens, tick[0], weights, tick[1], experts,
                              tick[2], interpret=interpret)
    return _grouped_fwd(tokens, weights, place, tile_expert, n_tiles,
                        experts, pair_slot, tick, tile, interpret)[0]


def _grouped_fwd(tokens, weights, place, tile_expert, n_tiles, experts,
                 pair_slot, tick, tile, interpret):
    rows, pairs, row_w = _padded_lists(place, weights)
    res = (tokens, weights, rows, pairs, row_w, tile_expert, n_tiles, experts)

    def body(i, acc):
        idx, x, wt, w = _tile_operands(i, tile, tokens, rows, row_w,
                                       tile_expert, experts)
        y = _swiglu_tile(x, w["gate"], w["up"], w["down"], wt)
        if pair_slot is None:
            return acc.at[idx].add(y, mode="drop", unique_indices=True)
        # The barrier keeps the write out of the down product's fusion:
        # fused, XLA's product writes the list's rows itself and takes
        # 18.2 us a tile of 256 for 12.4 (v5e); apart, the write is a
        # copy of 1.8 us.
        return lax.dynamic_update_slice(acc, lax.optimization_barrier(y),
                                        (i * tile, 0))
    if pair_slot is None:
        with region("moe.experts"):
            return lax.fori_loop(0, n_tiles, body,
                                 jnp.zeros_like(tokens)), res
    M, D = tile_expert.shape[0] * tile, tokens.shape[1]
    with region("moe.experts"):
        lst = lax.dynamic_update_slice(
            lax.empty((M, D), tokens.dtype),
            jnp.zeros((tile, D), tokens.dtype), (M - tile, 0))
        lst = lax.fori_loop(0, n_tiles, body, lst)
    with region("moe.combine"):
        return sum(
            lst.at[pair_slot[:, j]].get(mode="promise_in_bounds").astype(
                jnp.float32)
            for j in range(pair_slot.shape[1])).astype(tokens.dtype), res


def _grouped_bwd(tile, interpret, res, d_out):
    tokens, weights, rows, pairs, row_w, tile_expert, n_tiles, experts = res

    def body(i, carry):
        d_tok, d_wt, d_exp = carry
        idx, x, wt, w = _tile_operands(i, tile, tokens, rows, row_w,
                                       tile_expert, experts)
        dy = jnp.take(d_out, idx, axis=0, mode="fill", fill_value=0)
        _, vjp = jax.vjp(_swiglu_tile, x, w["gate"], w["up"], w["down"], wt)
        dx, dg, du, dd, dwt = vjp(dy)
        d_tok = d_tok.at[idx].add(dx, mode="drop", unique_indices=True)
        # a padding row names pair T * k, past the end: dropped
        pair = lax.dynamic_slice(pairs, (i * tile,), (tile,))
        d_wt = d_wt.at[pair].set(dwt.astype(d_wt.dtype), mode="drop",
                                 unique_indices=True)
        e = tile_expert[i]
        d_exp = {k: d_exp[k].at[e].add(d.astype(d_exp[k].dtype))
                 for k, d in (("gate", dg), ("up", du), ("down", dd))}
        return d_tok, d_wt, d_exp

    with region("moe.experts"):
        zeros = (jnp.zeros_like(tokens), jnp.zeros_like(weights).reshape(-1),
                 jax.tree.map(jnp.zeros_like, experts))
        d_tok, d_wt, d_exp = lax.fori_loop(0, n_tiles, body, zeros)
    return (d_tok, d_wt.reshape(weights.shape), None, None, None, d_exp,
            None, None)


_grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def moe_layer_held(tokens: jax.Array, router: jax.Array, bias: jax.Array,
                   experts_held: dict, held_range: tuple[int, int],
                   shared: Any = None, *, k: int, renormalize: bool = True,
                   scale: float = 1.0, tile: int = 512, picks: bool = False,
                   first_row=None, kernel: bool = False, interpret=False):
    """This chip's part of a dropless top-k expert layer.

    ``tokens [T, D]``; ``router [D, E]`` keeps every expert's column and
    ``bias [E]`` its selection bias (:func:`topk_route`, sigmoid gate in
    float32); ``experts_held`` = ``{"gate", "up" [E_held, D, F], "down"
    [E_held, F, D]}`` are experts ``held_range = (first, last + 1)`` of
    the ``E``.  A token's pairs whose expert lies elsewhere contribute
    nothing here: on the expert-parallel job they are computed by the
    chips that hold them and summed by the exchange, which a single
    chip's share runs without.  ``shared`` (``{"w_gate", "w_up",
    "w_down"}``) is the shared expert, which every chip computes alike.

    No capacity and no dropped token: the pairs held are sorted by
    expert and run as grouped products (:func:`_grouped_experts`) under
    the static bound ``T * min(k, E_held)`` that routing cannot pass.
    Where every expert the router scores is held (``E_held == E``) the
    products' results come back through a list of that bound's rows,
    else by an add a tile (:func:`combine_form`).

    From the gate's scores to the loop's lists everything but one sort
    is dense work over ``[T, E]``, ``[T, E_held]`` or ``[T, k]``: the
    choice by passes of first maximum (:func:`topk_route`), the counts
    and every pair's place in its expert's run by a running sum over the
    tokens of who took which held expert, each tile's expert by a
    comparison with the runs' ends; the sort then lays the pairs and
    their weights out by place (:func:`_padded_lists`).  A scatter or a
    gather of the ``T * k`` pairs, a scalar at a time, cost this chip
    1.2 to 2.7 ms each at 262,144 pairs and there were six or seven of
    them (13.4 ms a layer's routing, 1.3 now; 2.7 and 0.25 at 49,152).

    ``experts_held`` may be a stack of several layers' experts flattened
    on the leading axis (``[L * E_held, ...]``), ``first_row`` (a traced
    scalar) the row of this layer's first: each tile then picks its
    expert's matrices out of the whole stack where they lie, and no
    layer's ``[E_held, D, F]`` slice is copied out for the loop first
    (1.2 GB a layer and tick at 64 experts of 2048 x 1536, which XLA does
    copy when a scan over layers hands the loop its slice).

    ``tile`` is the rows of one grouped product: a caller with few rows
    (a decode tick) passes few, since an expert's run is padded to whole
    tiles and every tile reads its expert's three matrices.  ``kernel``
    says that the step is a decode tick, one position a row, whose caller
    runs its kernels (``interpret`` as theirs): where every scored expert
    is held the touched experts then stream through ``hvd_moe_decode``
    instead of the tiles (:func:`combine_form`'s ``"kernel"``), whose
    copies of the next expert's matrices run under this one's products;
    at a tick's rows the products are about 64 FLOPs a byte read, so the
    read sets the pace, and the tiles ran it at 67 to 73% of the
    bandwidth (v5e).

    Returns ``(out [T, D], stats)`` with ``stats = {"pairs_held": int32
    scalar, "expert_counts": int32 [E_held]}``, traced values for
    :func:`record_held_pairs`; with ``picks`` also ``"experts" [T, k]``
    and ``"scores" [T, E]``, the float32 gate activations the choice was
    made from (for a comparison with a reference's own choice).
    """
    T, D = tokens.shape
    first, last = held_range
    E_held = last - first
    assert first_row is not None or \
        experts_held["gate"].shape[0] == E_held, (
            held_range, experts_held["gate"].shape)
    with region("moe.route"):
        logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        experts, weights = topk_route(scores, bias, k,
                                      renormalize=renormalize, scale=scale)

        # A pair's key is its held expert; a pair held elsewhere gets
        # E_held.  A token takes an expert once, so a pair's place in its
        # expert's run is how many earlier tokens took that expert: a
        # running sum over the tokens of [T, E_held] zeros and ones.
        local = experts - first
        held = (local >= 0) & (local < E_held)
        key = jnp.where(held, local, E_held)                   # [T, k]
        bins = jnp.arange(E_held, dtype=jnp.int32)
        took = [key[:, j, None] == bins for j in range(k)]
        taken = sum(t.astype(jnp.int32) for t in took)         # [T, E_held]
        seen = jnp.cumsum(taken, axis=0)
        counts = seen[-1]
        # Expert e's run starts at a tile boundary of the M rows of all
        # tiles; tile i is of the expert whose run holds row i * tile: as
        # many runs as end at or before it.
        padded = -(-counts // tile) * tile
        ends = jnp.cumsum(padded)
        starts = ends - padded
        M = -(-T * min(k, E_held) // tile) * tile + E_held * tile
        n_tiles = ends[-1] // tile
        tile_expert = jnp.minimum(jnp.sum(
            jnp.arange(M // tile, dtype=jnp.int32)[:, None] * tile >= ends,
            axis=1, dtype=jnp.int32), E_held - 1)
        if first_row is not None:
            tile_expert = tile_expert + first_row
        # Each pair's row among the M, in pair order, and the rows that
        # pad an expert's last tile, for the one sort that lays out the
        # loop's lists (_padded_lists), whose length is past every tile
        # in use.
        n_list = T * k + E_held * tile
        before = seen - taken + starts
        slot = jnp.stack([jnp.sum(jnp.where(t, before, 0), axis=1)
                          for t in took], axis=1)
        spare = jnp.arange(tile, dtype=jnp.int32)
        place = jnp.concatenate([
            jnp.where(held, slot, n_list).reshape(-1),
            jnp.where(spare < (padded - counts)[:, None],
                      (starts + counts)[:, None] + spare, n_list).reshape(-1)])
        # Where the results come back through the list a pair reads its
        # own row of it; a pair held elsewhere the list's last row.  The
        # kernel form keeps the list's operands for a gradient's forward.
        form = combine_form(E_held, router.shape[1], kernel)
        pair_slot = tick = None
        if form != "add":
            pair_slot = jnp.where(held, slot, M - 1)
        if form == "kernel":
            tick = (local, counts, jnp.asarray(
                0 if first_row is None else first_row, jnp.int32))
    out = _grouped_experts(tokens, weights, place, tile_expert, n_tiles,
                           experts_held, pair_slot, tick, tile, interpret)
    if shared is not None:
        with region("moe.shared"):
            hidden = jax.nn.silu(tokens @ shared["w_gate"]) * \
                (tokens @ shared["w_up"])
            out = out + hidden @ shared["w_down"]
    stats = {"pairs_held": jnp.sum(counts), "expert_counts": counts}
    if picks:
        stats.update(experts=experts, scores=scores)
    return out, stats
