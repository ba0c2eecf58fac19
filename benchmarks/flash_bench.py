"""Flash-vs-dense attention microbenchmark (the data behind the
``ops/flash_attention.py`` speedup claims).

Run on the target backend (the TPU); appends one record
per sequence length to ``benchmarks/measured.jsonl`` so every speedup
number quoted in the tree points at committed data.

Usage: python benchmarks/flash_bench.py [--seqs 1024 2048 4096] [--no-persist]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


from benchmarks._common import fence as _fence, persist as _persist  # noqa: E402


def _time_it(fn, *args, iters: int = 50, warmup: int = 3) -> float:
    for _ in range(warmup):
        out = fn(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - t0) / iters


def _time_it_multi(fn, *args, iters: int = 50, warmup: int = 3) -> float:
    """Same, for functions returning a tuple of arrays (grads)."""
    for _ in range(warmup):
        out = fn(*args)
    _fence(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _fence(out[0])
    return (time.perf_counter() - t0) / iters


def run(seqs, persist: bool = True, causal: bool = True):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    backend = jax.default_backend()
    device_kind = getattr(jax.devices()[0], "device_kind", backend)
    B, H, D = 4, 16, 64
    scale = D ** -0.5
    records = []
    for S in seqs:
        key = jax.random.PRNGKey(S)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, S, H, D), jnp.bfloat16)

        dense = jax.jit(lambda q, k, v: fa.dense_attention(
            q, k, v, scale, causal))
        flash = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal))
        # Training shape: forward + backward through the attention (what
        # the flagship's train step actually pays — the flash backward
        # recomputes score blocks instead of materializing the [S, S]
        # softmax residuals the dense VJP hauls through HBM).
        dense_vg = jax.jit(jax.grad(lambda q, k, v: fa.dense_attention(
            q, k, v, scale, causal).astype(jnp.float32).sum(), (0, 1, 2)))
        flash_vg = jax.jit(jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal).astype(jnp.float32).sum(), (0, 1, 2)))

        t_dense = _time_it(dense, q, k, v)
        t_flash = _time_it(flash, q, k, v)
        t_dense_vg = _time_it_multi(dense_vg, q, k, v)
        t_flash_vg = _time_it_multi(flash_vg, q, k, v)
        rec = {
            "metric": f"flash_attention_speedup_{backend}",
            "seq_len": S, "B": B, "H": H, "D": D, "dtype": "bfloat16",
            "causal": causal,
            "fwd": {"dense_ms": round(t_dense * 1e3, 3),
                    "flash_ms": round(t_flash * 1e3, 3),
                    "speedup": round(t_dense / t_flash, 2)},
            "fwd_bwd": {"dense_ms": round(t_dense_vg * 1e3, 3),
                        "flash_ms": round(t_flash_vg * 1e3, 3),
                        "speedup": round(t_dense_vg / t_flash_vg, 2)},
            "device_kind": device_kind, "ts": time.time(),
        }
        records.append(rec)
        print(json.dumps(rec))
    if persist:
        for rec in records:
            _persist(rec)
    return records


def _chain_time(make_body, example, iters: int = 20, warmup: int = 2,
                repeats: int = 3):
    """Time ``iters`` serialized in-jit applications of an op.

    Per-call wall timing is dispatch-bound (the enqueue of a call dwarfs
    a sub-ms kernel — the round-5 trace showed in-model flash device
    times 3x below the old per-call walls), so the op is chained inside
    ONE jit via a data dependence (q += 1e-30 * out; nonzero so XLA
    cannot fold the op away) and the whole chain is fenced once.  The
    chain is timed ``repeats`` times and the MIN taken: a single
    multi-second fenced call is exposed to host hiccups (the first run
    of this harness produced fwd_bwd < fwd at one length and the
    opposite sign at the next — pure noise)."""
    import jax

    @jax.jit
    def many(q):
        def body(c, _):
            return c + 1e-30 * make_body(c), None
        out, _ = jax.lax.scan(body, q, None, length=iters)
        return out

    for _ in range(warmup):
        out = many(example)
    _fence(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = many(example)
        _fence(out)
        best = min(best, time.perf_counter() - t0)
    return best / iters


def run_gqa(seqs, persist: bool = True, rep: int = 4):
    """GQA-native kernel vs repeat-expanded K/V (round-4 verdict ask #1a):
    same math, but the native path keeps K/V at kv_heads in HBM and
    indexes groups inside the kernel."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    backend = jax.default_backend()
    device_kind = getattr(jax.devices()[0], "device_kind", backend)
    B, H, D = 8, 16, 64
    KV = H // rep
    records = []
    for S in seqs:
        key = jax.random.PRNGKey(S)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, S, KV, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, S, KV, D), jnp.bfloat16)

        def fwd_native(qq):
            return fa.flash_attention(qq, k, v)

        def fwd_expand(qq):
            kk_, vv_ = (jnp.repeat(k, rep, axis=2),
                        jnp.repeat(v, rep, axis=2))
            return fa.flash_attention(qq, kk_, vv_)

        # grads w.r.t. q AND k/v — and dk/dv folded into the chain value,
        # else XLA dead-code-eliminates the dkv kernel (the whole point
        # of the backward comparison; bug in this harness's first run).
        def _mix(grads):
            dq, dk, dv = grads
            return dq * (1.0 + dk.astype(jnp.float32).mean()
                         + dv.astype(jnp.float32).mean()).astype(dq.dtype)

        def bwd_native(qq):
            g = jax.grad(lambda x, kk_, vv_: fa.flash_attention(
                x, kk_, vv_).astype(jnp.float32).sum(), (0, 1, 2))(qq, k, v)
            return _mix(g)

        def bwd_expand(qq):
            def loss(x, kk_, vv_):
                return fa.flash_attention(
                    x, jnp.repeat(kk_, rep, axis=2),
                    jnp.repeat(vv_, rep, axis=2)).astype(jnp.float32).sum()
            return _mix(jax.grad(loss, (0, 1, 2))(qq, k, v))

        t_fn = _chain_time(fwd_native, q)
        t_fe = _chain_time(fwd_expand, q)
        t_bn = _chain_time(bwd_native, q)
        t_be = _chain_time(bwd_expand, q)
        rec = {
            "metric": f"flash_gqa_native_vs_expand_{backend}",
            "seq_len": S, "B": B, "H": H, "KV": KV, "D": D,
            "dtype": "bfloat16", "causal": True,
            "fwd": {"expand_ms": round(t_fe * 1e3, 3),
                    "native_ms": round(t_fn * 1e3, 3),
                    "speedup": round(t_fe / t_fn, 2)},
            "fwd_bwd": {"expand_ms": round(t_be * 1e3, 3),
                        "native_ms": round(t_bn * 1e3, 3),
                        "speedup": round(t_be / t_bn, 2)},
            "timing": "chained-in-jit device-dominated (see _chain_time)",
            "device_kind": device_kind, "ts": time.time(),
        }
        records.append(rec)
        print(json.dumps(rec))
    if persist:
        for rec in records:
            _persist(rec)
    return records


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="+",
                    default=[1024, 2048, 4096])
    ap.add_argument("--no-persist", action="store_true")
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--gqa", action="store_true",
                    help="GQA-native vs repeat-expanded K/V A/B")
    ap.add_argument("--rep", type=int, default=4,
                    help="q heads per kv head for --gqa")
    args = ap.parse_args()
    if args.gqa:
        run_gqa(args.seqs, persist=not args.no_persist, rep=args.rep)
    else:
        run(args.seqs, persist=not args.no_persist,
            causal=not args.non_causal)
