"""The plain float32 reference, and the comparisons that decide ``correct``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching tricks, and nothing imported from the
program.  Weights come from :mod:`chipbench.weights` again, from the seed,
one layer at a time, so the reference runs in blocks (layer by layer, row
by row) next to nothing else on the chip.

``quant="fp8"`` is the control: the same reference with every matmul
operand rounded through float8_e4m3 (per-tensor scale), the precision
below the bfloat16 the configurations state.  A comparison that the
control passes cannot see a later PR dropping to fp8.

Model: pre-norm decoder, RMSNorm (eps from the config), rotary embedding
in the half-split convention of the published Mistral/Llama code,
grouped-query causal attention, SwiGLU, untied head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

F32 = jnp.float32


def dims_of(config: dict) -> dict:
    """Sizes from a configuration file's published keys."""
    m = config
    return {
        "d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
        "rope_theta": float(m["rope_theta"]),
        "rms_norm_eps": float(m["rms_norm_eps"]),
    }


# -- the arithmetic ---------------------------------------------------------

def _fp8(x):
    """Round through float8_e4m3 with a per-tensor scale.  The backward
    pass sees the rounded operands but its own signal goes through
    unrounded: cast to fp8 without a scale, a gradient of 1e-6 is 0."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision="highest",
                      preferred_element_type=F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x [S, H, Dh]; rotate (x1, x2) halves, positions 0..S-1.
    S, _, Dh = x.shape
    half = Dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_fn(w: dict, h, dims: dict, quant=None):
    """One decoder layer on one sequence h [S, D] (float32)."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    H, KV, Dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    S = h.shape[0]
    x = _rmsnorm(h, w["attn_norm"], eps)
    q = _rope(_mm("sd,dhk->shk", x, w["wq"], quant), theta)
    k = _rope(_mm("sd,dhk->shk", x, w["wk"], quant), theta)
    v = _mm("sd,dhk->shk", x, w["wv"], quant)
    qg = q.reshape(S, KV, H // KV, Dh)
    s = _mm("sgrk,tgk->grst", qg, k, quant) / np.sqrt(Dh)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("grst,tgk->sgrk", p, v, quant).reshape(S, H, Dh)
    h = h + _mm("shk,hkd->sd", a, w["wo"], quant)
    x = _rmsnorm(h, w["mlp_norm"], eps)
    g = jax.nn.silu(_mm("sd,df->sf", x, w["w_gate"], quant))
    u = _mm("sd,df->sf", x, w["w_up"], quant)
    return h + _mm("sf,fd->sd", g * u, w["w_down"], quant)


def head_fn(w: dict, h, dims: dict, quant=None):
    """Final norm and head on h [S, D] -> logits [S, V]."""
    x = _rmsnorm(h, w["final_norm"].astype(F32), dims["rms_norm_eps"])
    return _mm("sd,dv->sv", x, w["lm_head"].astype(F32), quant)


def _frozen(d: dict):
    return tuple(sorted(d.items()))


@functools.lru_cache(maxsize=None)
def _programs(fdims, dtype_name: str, quant):
    """The jitted blocks for one model shape (built once per process)."""
    dims, dtype = dict(fdims), jnp.dtype(dtype_name)

    make_layer = jax.jit(lambda key, i: weights.layer(key, i, dims, dtype))
    make_outer = jax.jit(lambda key: weights.outer(key, dims, dtype))

    # rows [G, R, S, D]: G groups (one per device when sharded), R rows
    # each, one row at a time inside a group.
    def per_row(f):
        return jax.vmap(lambda rows: jax.lax.map(f, rows))

    @jax.jit
    def fwd(w, rows):
        return per_row(lambda h: layer_fn(w, h, dims, quant))(rows)

    @jax.jit
    def bwd(w, rows, d_out):
        w32 = jax.tree.map(lambda a: a.astype(F32), w)

        def group(rows_g, d_g):
            def one(acc, hd):
                h, d = hd
                _, vjp = jax.vjp(
                    lambda ww, hh: layer_fn(ww, hh, dims, quant), w32, h)
                dw, dh = vjp(d)
                return jax.tree.map(jnp.add, acc, dw), dh
            zero = jax.tree.map(jnp.zeros_like, w32)
            return jax.lax.scan(one, zero, (rows_g, d_g))
        dw, d_in = jax.vmap(group)(rows, d_out)
        return jax.tree.map(lambda a: a.sum(0), dw), d_in

    @jax.jit
    def head_loss(w, rows, targets, scale):
        """Summed token loss, its gradients for the head's leaves and for
        the incoming hidden state; ``scale`` is 1/(all tokens)."""
        w32 = {k: w[k].astype(F32) for k in ("final_norm", "lm_head")}

        def loss_row(ww, h, t):
            logits = head_fn(ww, h, dims, quant)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
            return jnp.sum(lse - picked) * scale

        def group(rows_g, t_g):
            def one(acc, ht):
                h, t = ht
                loss, (dw, dh) = jax.value_and_grad(
                    loss_row, argnums=(0, 1))(w32, h, t)
                return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], dw)), dh
            zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, w32))
            return jax.lax.scan(one, zero, (rows_g, t_g))
        (loss, dw), d_in = jax.vmap(group)(rows, targets)
        return loss.sum(), jax.tree.map(lambda a: a.sum(0), dw), d_in

    @jax.jit
    def logits_at(w, rows, pos):
        """Head logits at the listed positions of each row [K, n, V]."""
        def one(hp):
            h, p = hp
            return head_fn(w, h[p], dims, quant)
        return jax.lax.map(one, (rows, pos))

    @jax.jit
    def embed(w_embed, tokens):
        return w_embed.astype(F32)[tokens]

    @jax.jit
    def embed_grad(w_embed, tokens, d_rows):
        z = jnp.zeros(w_embed.shape, F32)
        return z.at[tokens.reshape(-1)].add(
            d_rows.reshape(-1, d_rows.shape[-1]))

    return dict(make_layer=make_layer, make_outer=make_outer, fwd=fwd,
                bwd=bwd, head_loss=head_loss, logits_at=logits_at,
                embed=embed, embed_grad=embed_grad)


# -- serving: the gap of each served token under the reference ---------------

def served_gaps(seed: int, dims: dict, dtype_name: str, samples: list,
                control: bool = False) -> dict:
    """``samples``: (prompt ids, served ids) pairs.  One teacher-forced
    pass over prompt + served tokens per sample; at every served position
    the gap by which the served token's reference logit lies below the
    reference's best.  With ``control`` also the gap of the token the fp8
    reference puts first at the same positions."""
    key = weights.root_key(seed)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)]) for p, t in samples]
    n_out = max(len(t) for _, t in samples)
    S = -(-max(len(s) for s in seqs) // 256) * 256
    toks = np.zeros((len(seqs), S), np.int32)
    pos = np.zeros((len(seqs), n_out), np.int32)
    served = np.zeros((len(seqs), n_out), np.int32)
    valid = np.zeros((len(seqs), n_out), bool)
    for i, (p, t) in enumerate(samples):
        toks[i, :len(seqs[i])] = seqs[i]
        # served token j was picked from the logits at position P-1+j
        pos[i, :len(t)] = len(p) - 1 + np.arange(len(t))
        served[i, :len(t)] = t
        valid[i, :len(t)] = True

    def logits(quant):
        pr = _programs(_frozen(dims), dtype_name, quant)
        outer = pr["make_outer"](key)
        rows = pr["embed"](outer["embed"], toks)[None]
        for i in range(dims["n_layers"]):
            rows = pr["fwd"](pr["make_layer"](key, i), rows)
        return pr["logits_at"](outer, rows[0], pos)

    ref = logits(None)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    out = {"served_gap": np.asarray(gap)[valid],
           "n_tokens": int(valid.sum())}
    if control:
        low = jnp.argmax(logits("fp8"), -1)
        cgap = best - jnp.take_along_axis(ref, low[..., None], -1)[..., 0]
        out["control_gap"] = np.asarray(cgap)[valid]
    return out


# -- training: a few steps of AdamW, layer by layer ---------------------------

def _adamw(p, g, hist, t: int, opt: dict):
    """AdamW's step ``t`` (optax.adamw: bias-corrected moments, decoupled
    decay) with the moments rebuilt from the gradients of steps 1..t:
    ``hist`` holds those before ``t``, oldest first."""
    b1, b2 = opt["b1"], opt["b2"]
    gs = list(hist) + [g]
    m = sum((1 - b1) * b1 ** (t - s) * x for s, x in enumerate(gs, 1))
    v = sum((1 - b2) * b2 ** (t - s) * x * x for s, x in enumerate(gs, 1))
    mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    u = mhat / (jnp.sqrt(vhat) + opt["eps"]) + opt["weight_decay"] * p
    return p - opt["learning_rate"] * u


@functools.partial(jax.jit, static_argnames=("t", "opt"), donate_argnums=0)
def _adamw_tree(p: dict, g: dict, hist: list, t: int, opt: tuple):
    o = dict(opt)
    return {k: _adamw(p[k], g[k], [h[k] for h in hist], t, o) for k in p}


def _norms(tree: dict, prefix: str) -> dict:
    return {prefix + k: float(jnp.sqrt(jnp.sum(jnp.square(a))))
            for k, a in tree.items()}


def train_readings(seed: int, dims: dict, dtype_name: str, batches: list,
                   opt: dict, devices, quant=None, rows=None,
                   history: str = "host") -> dict:
    """Follow ``len(batches)`` AdamW steps from the seed's weights.

    ``batches``: int32 [rows, S+1] token arrays, the global batch of each
    step.  ``rows`` (a slice) plants the fault of a step that sees only
    part of the batch and takes its mean over that part.  Returns the
    loss of each step, the norm of every leaf's first gradient and of
    every leaf's change after the last step.

    Memory: the float32 parameters stay on the device (5.4 GB for the
    training configuration).  Parameters with both Adam moments are 16.3
    GB, more than the chip has, so each layer's moments are rebuilt from
    the gradients of the earlier steps when its turn comes.  ``history``
    says where those wait: ``device`` (two steps fit: 10.9 GB with the
    parameters) or ``host`` (any number of steps, at the host link's
    speed: 1 GB/s measured on the v5e).  With several devices the rows
    are split over them and the parameters replicated; the compiler adds
    the one sum.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    pr = _programs(_frozen(dims), dtype_name, quant)
    key = weights.root_key(seed)
    n_rows = len(np.asarray(batches[0])[rows if rows is not None
                                         else slice(None)])
    G = math.gcd(len(devices), n_rows)        # groups of rows, one a device
    mesh = Mesh(np.array(devices[:G]), ("g",))
    split = NamedSharding(mesh, P("g"))
    repl = NamedSharding(mesh, P())
    L = dims["n_layers"]
    fopt = tuple(sorted((k, float(opt[k])) for k in
                        ("learning_rate", "b1", "b2", "eps", "weight_decay")))

    def as32(tree):
        return jax.device_put(
            jax.tree.map(lambda a: a.astype(F32), tree), repl)

    assert history in ("host", "device"), history
    names = list(range(L)) + ["outer"]
    params = {i: as32(pr["make_layer"](key, i)) for i in range(L)}
    params["outer"] = as32(pr["make_outer"](key))
    history_of = {n: [] for n in names}           # name -> [gradient tree]
    pending = []                                  # downloads in flight

    def settle(keep: int):
        while len(pending) > keep:
            n, tree = pending.pop(0)
            history_of[n].append(jax.tree.map(np.asarray, tree))

    def update(name, g, t, last):
        hist = history_of[name]
        if history == "host":
            hist = [jax.device_put(h, repl) for h in hist]
        params[name] = _adamw_tree(params[name], g, hist, t, fopt)
        if last:
            history_of[name].clear()
        elif history == "host":
            jax.tree.map(lambda a: a.copy_to_host_async(), g)
            pending.append((name, g))
            settle(keep=1)
        else:
            history_of[name].append(g)

    out = {"loss": [], "grad_norm": {}, "delta_norm": {}}
    for t, batch in enumerate(batches, start=1):
        batch = np.asarray(batch, np.int32)
        if rows is not None:
            batch = batch[rows]
        R, S1 = batch.shape
        assert R % G == 0, (R, G)
        grouped = batch.reshape(G, R // G, S1)
        tokens = jax.device_put(grouped[..., :-1], split)
        targets = jax.device_put(grouped[..., 1:], split)
        last = t == len(batches)

        acts = [pr["embed"](params["outer"]["embed"], tokens)]
        for i in range(L):
            acts.append(pr["fwd"](params[i], acts[-1]))
        loss, d_head, d = pr["head_loss"](
            params["outer"], acts.pop(), targets, 1.0 / (R * (S1 - 1)))
        out["loss"].append(float(loss))
        for i in reversed(range(L)):
            g, d = pr["bwd"](params[i], acts.pop(), d)
            if t == 1:
                out["grad_norm"].update(_norms(g, f"L{i}."))
            update(i, g, t, last)
            del g
        g = dict(d_head, embed=pr["embed_grad"](
            params["outer"]["embed"], tokens, d))
        if t == 1:
            out["grad_norm"].update(_norms(g, ""))
        update("outer", g, t, last)
        del g, d
        settle(keep=0)

    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y.astype(F32)))), a, b))
    for i in range(L):
        d = diff(params.pop(i), pr["make_layer"](key, i))
        out["delta_norm"].update({f"L{i}.{k}": float(x)
                                  for k, x in d.items()})
    d = diff(params.pop("outer"), pr["make_outer"](key))
    out["delta_norm"].update({k: float(x) for k, x in d.items()})
    return out


# -- the comparison ----------------------------------------------------------

def worst_leaf_gap(program: dict, ref: dict, keep=None) -> tuple:
    """Largest |program norm - reference norm| over the leaves, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger.  Returns (gap, leaf)."""
    names = [k for k in ref if keep is None or k in keep]
    floor = float(np.median([ref[k] for k in names]))
    gaps = {k: abs(program[k] - ref[k]) / max(ref[k], floor) for k in names}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def moving_leaves(ref_grad_norm: dict) -> set:
    """Leaves whose first gradient is not nought to rounding: at least a
    thousandth of the median leaf's.  The rest move under Adam by
    round-off alone and are left out of the change."""
    floor = 1e-3 * float(np.median(list(ref_grad_norm.values())))
    return {k for k, g in ref_grad_norm.items() if g >= floor}


def compare_training(program: dict, ref: dict) -> dict:
    """The numbers a training cell is held to, by short plain names."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], ref["loss"]), start=1):
        out[f"loss{i}_rel"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(
        program["grad_norm"], ref["grad_norm"])
    out["delta_norm_gap"], out["delta_norm_leaf"] = worst_leaf_gap(
        program["delta_norm"], ref["delta_norm"],
        keep=moving_leaves(ref["grad_norm"]))
    return out
