"""The serving driver for a looped decoder (Ouro): the loop of
:mod:`chipbench.drivers.serve` over ``weights_ouro``, ``reference_ouro``
and a ``LlamaConfig`` with the loop, the sandwich norms and the file's
epsilon.  (The two drivers want one loop; ``serve.py`` is bound to its
three names by import and is not this PR's to edit: PERF.md, section 7.)

What differs beside the names, because this cell's pool and not its slots
bounds the batch, so requests are preempted and prefilled again:

- set-up also warms every (prefill bucket, block count) a resumed
  request's prefill can have, up to the longest context of the mix: a
  preempted request comes back with its prompt and what it had generated,
  a length the mix's own prompts never have, and the engine compiles one
  scatter for each such pair;
- a turn's record tells a resumed request's prefill (``resumed``: the
  tokens prefilled again; its one emitted token counts as output) from the
  decode tick's streams, so that the costs count a tick's real streams
  and a prompt's tokens once;
- the check adds the reference's exit sum: no checked token may have
  left before the last pass.
"""

from __future__ import annotations

import collections
import functools
import sys
import time

import numpy as np

from .. import loadgen, reference_ouro as reference, weights_ouro as weights
from .serve import _warm_lengths


def ouro_config(dims: dict, dtype):
    """The program's ``LlamaConfig`` for the configuration file."""
    from horovod_tpu.models import llama
    try:
        lcfg = llama.LlamaConfig(
            vocab_size=dims["vocab_size"], d_model=dims["d_model"],
            n_layers=dims["n_layers"], n_heads=dims["n_heads"],
            n_kv_heads=dims["n_kv_heads"], d_ff=dims["d_ff"],
            rope_theta=dims["rope_theta"], dtype=dtype, remat=False,
            loops=dims["loops"], sandwich_norm=True,
            rms_eps=dims["rms_norm_eps"])
    except TypeError as e:
        raise SystemExit("chipbench: this checkout's LlamaConfig cannot "
                         f"state a looped stack ({e}); nothing ran") from None
    assert lcfg.head_dim == dims["head_dim"], (lcfg.head_dim, dims)
    return lcfg


def _resumed_lengths(traffic: dict, eng: dict, have: list) -> list:
    """One prompt length for every (prefill bucket, block count) that a
    preempted request's second prefill can have and ``have`` lacks: it
    comes back as its prompt and what it had generated, any length up to
    the mix's longest context."""
    bs, buckets = eng["block_size"], eng["prefill_buckets"]
    bucket = lambda n: next((b for b in buckets if n <= b), n)
    shape = lambda n: (bucket(n), -(-n // bs))
    lo = min(loadgen.levels(traffic["prompt"]))
    top = max(loadgen.levels(traffic["prompt"])) + \
        max(loadgen.levels(traffic["output"])) - 1
    seen, out = {shape(p) for p in have}, []
    for n in range(top, lo, -1):           # longest first: its table width
        if shape(n) not in seen:
            seen.add(shape(n))
            out.append(n)
    return out


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.obs import REGISTRY

    cfg, tr = run.config, run.traffic
    dims = weights.dims_of(cfg)
    dtype_name = cfg["torch_dtype"]
    dtype = jnp.dtype(dtype_name)
    lcfg = ouro_config(dims, dtype)
    eng = dict(cfg["engine"])
    eng["prefill_buckets"] = tuple(eng.get("prefill_buckets", ()))
    key = weights.root_key(run.seed)
    params = jax.jit(lambda k: weights.stacked(k, dims, dtype))(key)
    jax.block_until_ready(params)
    run.mark("weights")
    session = serving.serve(params, lcfg, **eng)
    want = cfg.get("attention_path")
    got = session.engine.attention_path
    assert want in (None, got), f"decode attention path {got!r}, not {want!r}"
    assert session.engine.cache.n_layers == dims["loops"] * dims["n_layers"]
    occupancy = REGISTRY.get("hvd_serving_batch_occupancy")
    preemptions = REGISTRY.get("hvd_serving_preemptions_total")

    arrivals = tr["arrivals"]
    assert arrivals["kind"] == "backlog", "serve_ouro drives backlog mixes"
    reqs = loadgen.schedule(tr, run.seed, dims["vocab_size"], run.seconds)

    # -- warm-up ---------------------------------------------------------
    wrng = loadgen.rng_for(run.seed, 7)
    lens = _warm_lengths(tr, eng["block_size"])
    for p in lens + _resumed_lengths(tr, eng, lens):
        session.submit(wrng.integers(0, dims["vocab_size"], size=p,
                                     dtype=np.int32), 2)
        session.drain()
    run.mark("warm")

    # -- the loop --------------------------------------------------------
    recs = [dict(prompt=r["prompt"], prompt_len=len(r["prompt"]),
                 max_tokens=r["max_tokens"], t_last=None, streamed=[],
                 fut=None) for r in reqs]
    emits: list = []                       # (index, tokens before) this step
    steps: list = []                       # per engine turn

    def on_token(i, _req_id, token):
        rec = recs[i]
        rec["t_last"] = time.perf_counter()
        emits.append((i, len(rec["streamed"])))
        rec["streamed"].append(int(token))

    def record(t0, t1):
        """One turn: first prefills by prompt length, resumed prefills by
        the tokens prefilled again, the tick's streams by context.  A
        request that emits twice in a turn was prefilled in it (the tick
        that follows takes it along); its first token there is the
        prefill's."""
        count = collections.Counter(i for i, _ in emits)
        twice = {i for i, n in count.items() if n > 1}
        st = dict(t0=t0, t1=t1, occupancy=occupancy.value, prefill=[],
                  resumed=[], decode=[])
        for i, k in emits:
            plen = recs[i]["prompt_len"]
            if i in twice:                 # the prefill's own token
                twice.discard(i)
                st["resumed" if k else "prefill"].append(plen + k)
            elif k == 0:                   # no tick followed (one token)
                st["prefill"].append(plen)
            else:
                st["decode"].append(plen + k)
        steps.append(st)
        emits.clear()

    t_open = run.open_window()             # the clock the window uses
    preempted_at_open = preemptions.value
    for i, r in enumerate(reqs):           # a backlog: all due at once
        with run.span("submit"):
            recs[i]["fut"] = session.submit(
                r["prompt"], r["max_tokens"],
                stream_cb=functools.partial(on_token, i))
    while True:
        t_close = run.poll()
        if t_close - t_open >= run.seconds:
            run.close_window()
            break
        if session.engine.has_work():
            t0 = time.perf_counter()
            with run.span("engine.step"):
                session.drain(max_steps=1)
            record(t0, time.perf_counter())
        else:
            with run.span("loadgen.wait"):
                time.sleep(0.001)
    wall = t_close - t_open
    preempted = int(preemptions.value - preempted_at_open)

    # -- accounting ------------------------------------------------------
    done = [r for r in recs if r["fut"] is not None and r["fut"].done()]
    bad = 0
    for r in done:
        try:
            r["result"] = r["fut"].result(timeout=0)
        except Exception as e:                       # a failed request
            r["error"] = repr(e)
            bad += 1
            continue
        if "error" in r["result"].metrics:
            r["error"] = r["result"].metrics["error"]
    served = lambda r: "result" in r and "error" not in r
    in_win = lambda t: t is not None and t_open <= t <= t_close
    inside = [st for st in steps if in_win(st["t1"])]
    # prompt tokens of first prefills, and every output token emitted: a
    # resumed prefill's tokens were counted when they were first made
    tokens = sum(sum(st["prefill"]) + len(st["prefill"])
                 + len(st["resumed"]) + len(st["decode"]) for st in inside)
    again = sum(sum(st["resumed"]) for st in inside)
    pool = [r for r in done if in_win(r["t_last"])]
    ok = [r for r in pool if served(r)]
    attempted, failed = len(pool), len(pool) - len(ok)
    counters = dict(
        steps=steps, t_open=t_open, t_close=t_close, window_wall_s=wall,
        block_size=eng["block_size"], max_active=eng["max_active"],
        tokens_in_window=tokens, preemptions_in_window=preempted,
        reprefill_tokens_in_window=again)

    # -- the sample the reference is run over ----------------------------
    good = [r for r in recs if served(r)]
    k = min(int(tr["check_requests"]), len(good))
    longest = max(good, key=lambda r: r["prompt_len"] + len(r["streamed"]),
                  default=None)
    others = [r for r in good if r is not longest]
    pick = loadgen.rng_for(run.seed, 6).permutation(
        len(others))[:max(k - 1, 0)]
    sample = ([longest] if longest else []) + [others[i] for i in pick]
    samples = [(r["prompt"], list(r["result"].tokens)) for r in sample]
    wrong = sum(1 for r in good
                if list(r["result"].tokens) != r["streamed"]
                or len(r["streamed"]) != r["max_tokens"])

    state = {"session": session, "params": params}

    def release():
        state["session"].close()
        state.clear()
        for r in recs:
            r["fut"] = None
            r.pop("result", None)

    def check(control: bool):
        lim = tr["limits"]
        # under the threshold by one float64 step: "has not reached it"
        exit_limit = float(np.nextafter(dims["exit_threshold"], 0.0))
        rows = [("requests_failed", float(failed + bad), 0.0),
                ("answers_altered", float(wrong), 0.0)]
        if not samples:
            return rows + [("served_gap_max", float("inf"),
                            lim["served_gap_max"])]
        t0 = time.perf_counter()
        gaps = reference.served_gaps(run.seed, dims, dtype_name, samples,
                                     control=control)
        run.counters["reference_s"] = time.perf_counter() - t0
        run.counters["checked_tokens"] = gaps["n_tokens"]
        rows += [("served_gap_max", float(gaps["served_gap"].max()),
                  lim["served_gap_max"]),
                 ("exit_sum_before_last_pass", gaps["exit_sum_max"],
                  exit_limit)]
        if control:
            c = gaps["control_gap"]
            run.controls["fp8"] = {
                "served_gap_max": float(c.max()),
                "flipped": int((c > 0).sum()), "tokens": gaps["n_tokens"]}
            print(f"[chipbench] control fp8: {run.controls['fp8']}; "
                  f"program flipped {(gaps['served_gap'] > 0).sum()}",
                  file=sys.stderr, flush=True)
        return rows

    n_ticks = sum(1 for s in inside if s["decode"])
    n_pre = sum(len(s["prefill"]) for s in inside)
    n_again = sum(len(s["resumed"]) for s in inside)
    return {
        "attempted": attempted, "failed": failed + bad,
        "end_to_end": {"serve_tokens_per_s": tokens / wall},
        "counters": counters,
        "notes": [f"window {wall:.3f} s: {n_pre} prefills, {n_again} "
                  f"resumed prefills of {again} tokens after {preempted} "
                  f"preemptions, {n_ticks} decode ticks, {tokens} tokens, "
                  f"{len(pool)} requests judged, {len(recs) - len(done)} "
                  f"still queued or running at the end; {len(samples)} "
                  f"requests ({sum(len(t) for _, t in samples)} served "
                  "tokens) go to the reference",
                  lambda: f"the reference took "
                  f"{run.counters.get('reference_s', 0):.1f} s"],
        "release": release, "check": check,
    }
