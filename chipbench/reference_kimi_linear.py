"""The plain float32 reference of the Kimi-Linear configuration, and the
readings a training cell is compared on.

As :mod:`chipbench.reference` (whose fp8 control, AdamW, head and
comparison it uses): straight ``jax.numpy`` in float32 at ``highest``
matmul precision, nothing imported from the program, weights made again
from the seed one layer at a time, one row at a time.

What is computed, per layer input ``x [S, D]`` (pre-norm residual stack,
RMSNorm, untied head, no positional encoding):

- **KDA mixer**: ``q, k, v = silu(causal_depthwise_conv(x W))``, q and k
  L2-normalised per head, q scaled by ``K^-0.5``; per-channel log decay
  ``g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)``; ``beta =
  sigmoid(x W_b)``; then **the recurrence itself, token by token**,
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t`` (not the chunked algorithm the program
  runs), as a ``lax.scan`` in checkpointed segments of 64 steps so that
  its gradient keeps one state a segment; output ``(rmsnorm(o) * o_norm
  * sigmoid((x W_ga) W_gb)) W_o``.
- **MLA mixer**: ``q = x W_q``; ``c, k_pe = split(x W_kva)``; ``k_nope, v
  = split(rmsnorm(c) W_kvb)``; ``k = [k_nope, k_pe]`` (``k_pe`` shared by
  the heads), no rotation; causal softmax attention at ``(nope +
  rope)^-0.5``, a head at a time.
- **MoE**: ``s = sigmoid(x W_r)`` (never rounded: the configuration
  routes in float32); the experts a token takes are the top k of ``s +
  b``; weights ``s`` there, renormalised, times the scaling factor.  The
  output is the sum over the taken experts **held here** of weight x
  SwiGLU(x), every held expert run over every token with the weight 0
  where it was not taken, plus the shared expert.  What the absent
  experts would add is left out, as in the program: the same share.

Departures from the published model, stated in the configuration file:
no auxiliary loss; and ``b`` is a seeded constant that nothing moves (the
published model moves it by a balancing rule outside the gradient).

``fault`` plants what the comparison has to catch: ``no_decay`` (decay
gate forced to 1), ``no_shared`` (shared expert left out),
``absent_added`` (the pairs routed to experts held elsewhere are not left
out: each is run through the held expert of the same index modulo the
number held, which adds a part of the size the absent experts' would
have).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as base, weights_kimi_linear as weights
from .reference import F32, _fp8, _mm, _rmsnorm

SEGMENT = 64          # steps of the recurrence between saved states


def recurrence(q, k, v, g, beta):
    """``o [S, H, V]`` of the gated delta rule, token by token."""
    S, H, K = q.shape
    seg = SEGMENT if S % SEGMENT == 0 else S

    def step(St, x):
        # products and sums over the state, not matrix products: a
        # one-row product at ``highest`` costs the MXU six passes a token
        qt, kt, vt, gt, bt = x
        St = jnp.exp(gt)[..., None] * St
        u = bt[:, None] * (vt - jnp.sum(kt[..., None] * St, axis=1))
        St = St + kt[..., None] * u[:, None, :]
        return St, jnp.sum(qt[..., None] * St, axis=1)

    segment = jax.checkpoint(
        lambda St, xs: jax.lax.scan(step, St, xs, unroll=8))
    xs = jax.tree.map(lambda a: a.reshape(S // seg, seg, *a.shape[1:]),
                      (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((H, K, v.shape[-1]), F32), xs)
    return o.reshape(S, H, v.shape[-1])


def kda_inputs(w, x, d, quant=None, fault=None):
    """``(q, k, v, g, beta)`` of one sequence, as the recurrence takes
    them."""
    S, K = x.shape[0], d["kda_head_dim"]

    def proj(wn, cn):
        y = _mm("sd,dhk->shk", x, w[wn], quant)
        kk = w[cn].shape[0]
        yp = jnp.pad(y, ((kk - 1, 0), (0, 0), (0, 0)))
        return jax.nn.silu(sum(w[cn][j] * yp[j:j + S] for j in range(kk)))

    l2 = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + d["l2_eps"])
    q, k, v = (l2(proj("wq", "conv_q")) * K ** -0.5,
               l2(proj("wk", "conv_k")), proj("wv", "conv_v"))
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        _low(w, x, "w_fa", "w_fb", quant) + w["dt_bias"])
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm("sd,dh->sh", x, w["w_beta"], quant))
    if quant == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    return q, k, v, g, beta


def _low(w, x, a, b, quant):
    return _mm("sr,rhk->shk", _mm("sd,dr->sr", x, w[a], quant), w[b], quant)


def kda_output(w, x, o, d, quant=None):
    o = _rmsnorm(o, w["o_norm"], d["rms_norm_eps"]) * \
        jax.nn.sigmoid(_low(w, x, "w_ga", "w_gb", quant))
    return _mm("shk,hkd->sd", o, w["wo"], quant)


def kda_mixer(w, x, d, quant=None, fault=None):
    o = recurrence(*kda_inputs(w, x, d, quant, fault))
    return kda_output(w, x, o, d, quant)


def mla_mixer(w, x, d, quant=None):
    S, C, nope = x.shape[0], d["kv_lora_rank"], d["qk_nope"]
    q = _mm("sd,dhk->shk", x, w["wq"], quant)
    kva = _mm("sd,dc->sc", x, w["w_kva"], quant)
    c = _rmsnorm(kva[:, :C], w["kv_norm"], d["rms_norm_eps"])
    kv = _mm("sc,chk->shk", c, w["w_kvb"], quant)
    k_pe = jnp.broadcast_to(kva[:, None, C:], (S, d["n_heads"], d["qk_rope"]))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    scale = 1.0 / math.sqrt(nope + d["qk_rope"])
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, _mm("sk,tk->st", qh, kh, quant) * scale,
                      -jnp.inf)
        return _mm("st,tk->sk", jax.nn.softmax(s, axis=-1), vh, quant)

    heads = lambda a: a.transpose(1, 0, 2)
    o = jax.lax.map(head, (heads(q), heads(k), heads(kv[..., nope:])))
    return _mm("hsk,hkd->sd", o, w["wo"], quant)


def _swiglu(x, wg, wu, wd, quant):
    hidden = jax.nn.silu(_mm("sd,df->sf", x, wg, quant)) * \
        _mm("sd,df->sf", x, wu, quant)
    return _mm("sf,fd->sd", hidden, wd, quant)


def route(w, x, d):
    """(experts [S, k], weights [S, k]) of every token, over all the
    router's outputs, in float32."""
    s = jax.nn.sigmoid(_mm("sd,de->se", x, w["router"], None))
    _, experts = jax.lax.top_k(
        jax.lax.stop_gradient(s + w["router_bias"]), d["experts_per_token"])
    wts = jnp.take_along_axis(s, experts, -1)
    if d["renormalize"]:
        wts = wts / wts.sum(-1, keepdims=True)
    return experts, wts * d["routed_scale"]


def moe_mlp(w, x, d, quant=None, fault=None):
    Eh, first = d["experts_held"], d["held_first"]
    experts, wts = route(w, x, d)
    local = experts % Eh if fault == "absent_added" else experts - first
    # weight of held expert e for token t: 0 where t did not take it
    per_expert = jnp.sum(jnp.where(
        local[..., None] == jnp.arange(Eh), wts[..., None], 0.0), axis=1)

    def one(acc, ew):
        wg, wu, wd, wt = ew
        return acc + wt[:, None] * _swiglu(x, wg, wu, wd, quant), None

    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          (w["e_gate"], w["e_up"], w["e_down"], per_expert.T))
    if fault != "no_shared":
        out = out + _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], quant)
    return out


def mixer_fn(w, h, kind: str, d: dict, quant=None, fault=None):
    x = _rmsnorm(h, w["attn_norm"], d["rms_norm_eps"])
    return h + (kda_mixer(w, x, d, quant, fault) if kind.startswith("kda")
                else mla_mixer(w, x, d, quant))


def mlp_fn(w, h, kind: str, d: dict, quant=None, fault=None):
    x = _rmsnorm(h, w["mlp_norm"], d["rms_norm_eps"])
    if kind.endswith("dense"):
        return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"], quant)
    return h + moe_mlp(w, x, d, quant, fault)


def layer_fn(w: dict, h, kind: str, d: dict, quant=None, fault=None):
    """One layer of ``kind`` on one sequence ``h [S, D]`` (float32)."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    return mlp_fn(w, mixer_fn(w, h, kind, d, quant, fault), kind, d, quant,
                  fault)


def layer_rows(w: dict, rows, kind: str, d: dict, quant=None, fault=None):
    """One layer on rows ``[R, S, D]``, one row at a time; the backward
    keeps a row's input and runs the row again."""
    return jax.lax.map(jax.checkpoint(
        lambda h: layer_fn(w, h, kind, d, quant, fault)), rows)


def forward_loss(layers: list, w: dict, tokens, d: dict, quant=None,
                 fault=None):
    """Mean next-token loss of ``tokens [R, S+1]`` in one piece: the
    whole-model oracle of the CPU tests (``layers``: each layer's
    weights; ``w``: the embedding, final norm and head)."""
    rows = w["embed"].astype(F32)[tokens[:, :-1]]
    for i, lw in enumerate(layers):
        rows = jax.vmap(lambda h: layer_fn(
            lw, h, weights.kind_of(d, i), d, quant, fault))(rows)
    logits = jax.vmap(lambda h: base.head_fn(w, h, d, quant))(rows)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - picked)


def _bites(kind: str, fault):
    """``fault`` if it changes a layer of ``kind``, else None (so that a
    kind it leaves alone shares its compiled blocks with the sound run)."""
    part = {"no_decay": "kda_", "no_shared": "_moe", "absent_added": "_moe"}
    return fault if fault and part[fault] in kind else None


@functools.lru_cache(maxsize=None)
def _layer_programs(fdims, kind: str, quant, fault):
    """Forward and backward of one kind of layer over rows ``[G, R, S,
    D]`` (G groups, one a device)."""
    d = dict(fdims)
    f = lambda w, rows: layer_rows(w, rows, kind, d, quant, fault)
    fwd = jax.jit(lambda w, rows: jax.vmap(lambda g: f(w, g))(rows))

    @jax.jit
    def bwd(w, rows, d_out):
        w32 = jax.tree.map(lambda a: a.astype(F32), w)

        def group(rows_g, d_g):
            _, vjp = jax.vjp(f, w32, rows_g)
            return vjp(d_g)
        dw, d_in = jax.vmap(group)(rows, d_out)
        return jax.tree.map(lambda a: a.sum(0), dw), d_in

    return fwd, bwd


@functools.lru_cache(maxsize=None)
def _programs(fdims, dtype_name: str, quant, fault):
    """The jitted blocks for one model shape: per kind of layer a forward
    and a backward, the head and the embedding of
    :mod:`chipbench.reference`, and the weights."""
    d, dtype = dict(fdims), jnp.dtype(dtype_name)
    shared = base._programs(base._frozen({"rms_norm_eps": d["rms_norm_eps"]}),
                            dtype_name, quant)
    kinds = {weights.kind_of(d, i) for i in range(d["n_layers"])}
    blocks = {k: _layer_programs(fdims, k, quant, _bites(k, fault))
              for k in kinds}
    return dict(
        shared, fwd={k: b[0] for k, b in blocks.items()},
        bwd={k: b[1] for k, b in blocks.items()},
        make_layer=_make_layer(fdims, dtype_name),
        make_outer=jax.jit(lambda key: weights.outer(key, d, dtype)))


@functools.lru_cache(maxsize=None)
def _make_layer(fdims, dtype_name: str):
    d, dtype = dict(fdims), jnp.dtype(dtype_name)
    return jax.jit(lambda key, i: weights.layer(key, i, d, dtype),
                   static_argnums=1)


FROZEN = ("router_bias",)     # leaves no optimizer moves


def train_readings(seed: int, dims: dict, dtype_name: str, batches: list,
                   opt: dict, devices, quant=None, rows=None, fault=None
                   ) -> dict:
    """Follow ``len(batches)`` AdamW steps from the seed's weights: the
    loss of each step, the norm of every leaf's first gradient and of
    every leaf's change after the last step (``L<i>.<leaf>``, 0-based
    layers; the selection bias, which nothing moves, left out).
    ``delta_norm_rounded`` is the first layer's change once more, with
    each step's float32 update added to a copy of its leaves kept in the
    types the seed makes them in (the matrices in ``dtype_name``): what
    rounding the weights alone does to the change.

    As :func:`chipbench.reference.train_readings` with layers of several
    kinds: float32 parameters stay on the device (3.3 GB), each layer's
    moments are rebuilt from the gradients of the earlier steps, kept on
    the device too (one step of history: 3.3 GB more)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    d = dims
    pr = _programs(base._frozen(d), dtype_name, quant, fault)
    key = weights.root_key(seed)
    take = rows if rows is not None else slice(None)
    n_rows = len(np.asarray(batches[0])[take])
    G = math.gcd(len(devices), n_rows)
    mesh = Mesh(np.array(devices[:G]), ("g",))
    split, repl = NamedSharding(mesh, P("g")), NamedSharding(mesh, P())
    L = d["n_layers"]
    kinds = [weights.kind_of(d, i) for i in range(L)]
    fopt = tuple(sorted((k, float(opt[k])) for k in
                        ("learning_rate", "b1", "b2", "eps", "weight_decay")))
    as32 = lambda tree: jax.device_put(
        jax.tree.map(lambda a: a.astype(F32), tree), repl)

    names = list(range(L)) + ["outer"]
    params = {i: as32(pr["make_layer"](key, i)) for i in range(L)}
    params["outer"] = as32(pr["make_outer"](key))
    history = {n: [] for n in names}
    moving = lambda tree: {k: v for k, v in tree.items() if k not in FROZEN}
    # the first layer's leaves once more, each in the type it is kept in
    rounded = moving(pr["make_layer"](key, 0))

    def update(name, g, t, last):
        p = params[name]
        still = {k: p.pop(k) for k in FROZEN if k in p}
        g = {k: v for k, v in g.items() if k not in FROZEN}
        if name == 0:       # the update gives up the buffers of ``p``
            before = jax.tree.map(jnp.copy, p)
        new = base._adamw_tree(p, g, history[name], t, fopt)
        if name == 0:
            rounded.update({k: (rounded[k].astype(F32) + (new[k] - before[k])
                                ).astype(rounded[k].dtype) for k in new})
        params[name] = dict(new, **still)
        history[name] = [] if last else history[name] + [g]

    out = {"loss": [], "grad_norm": {}, "delta_norm": {}}
    for t, batch in enumerate(batches, start=1):
        batch = np.asarray(batch, np.int32)[take]
        R, S1 = batch.shape
        grouped = batch.reshape(G, R // G, S1)
        tokens = jax.device_put(grouped[..., :-1], split)
        targets = jax.device_put(grouped[..., 1:], split)
        last = t == len(batches)

        acts = [pr["embed"](params["outer"]["embed"], tokens)]
        for i in range(L):
            acts.append(pr["fwd"][kinds[i]](params[i], acts[-1]))
        loss, d_head, dh = pr["head_loss"](
            params["outer"], acts.pop(), targets, 1.0 / (R * (S1 - 1)))
        out["loss"].append(float(loss))
        for i in reversed(range(L)):
            g, dh = pr["bwd"][kinds[i]](params[i], acts.pop(), dh)
            if t == 1:
                out["grad_norm"].update(base._norms(moving(g), f"L{i}."))
            update(i, g, t, last)
            del g
        g = dict(d_head, embed=pr["embed_grad"](
            params["outer"]["embed"], tokens, dh))
        if t == 1:
            out["grad_norm"].update(base._norms(g, ""))
        update("outer", g, t, last)
        del g, dh

    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y.astype(F32)))), a, b))
    dn = diff(jax.tree.map(lambda a: a.astype(F32), rounded),
              moving(pr["make_layer"](key, 0)))
    out["delta_norm_rounded"] = {f"L0.{k}": float(x) for k, x in dn.items()}
    for i in range(L):
        dn = diff(moving(params.pop(i)), moving(pr["make_layer"](key, i)))
        out["delta_norm"].update({f"L{i}.{k}": float(x)
                                  for k, x in dn.items()})
    dn = diff(params.pop("outer"), pr["make_outer"](key))
    out["delta_norm"].update({k: float(x) for k, x in dn.items()})
    return out


def compare_training(program: dict, ref: dict) -> dict:
    """:func:`chipbench.reference.compare_training`, and beside it the
    change gap over the first layer's leaves twice: against the float32
    reference (``delta_norm_gap_first``) and against its leaves kept
    in the types the program keeps them in
    (``delta_norm_gap_first_rounded``).  The second reads what is left
    of the first when the reference rounds its weights as the program
    does; ``delta_norm_first`` holds each leaf's three norms (program,
    float32, rounded)."""
    out = base.compare_training(program, ref)
    keep = base.moving_leaves(ref["grad_norm"])
    rounded = ref["delta_norm_rounded"]
    plain = {k: ref["delta_norm"][k] for k in rounded}
    for name, other in (("first", plain), ("first_rounded", rounded)):
        out[f"delta_norm_gap_{name}"], out[f"delta_norm_leaf_{name}"] = \
            base.worst_leaf_gap(program["delta_norm"], other, keep=keep)
    out["delta_norm_first"] = {
        k: [float(f"{x:.4g}") for x in
            (program["delta_norm"][k], plain[k], rounded[k])]
        for k in rounded}
    return out
