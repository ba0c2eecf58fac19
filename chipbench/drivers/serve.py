"""The serving driver: one loop for the backlog and the open-loop mixes.

The system under test is entered where a user enters it:
``serving.serve`` -> ``ServingSession.submit`` with a per-token callback,
and ``ServingSession.drain(max_steps=1)`` to turn the engine (the
synchronous mode the program documents for benchmarks).  One thread, no
children: the loop submits what is due, turns the engine once, and looks
at the clock.

Set-up makes the weights on the device from the seed, warms every prompt
length the mix can send (one prefill bucket, one scatter shape and one
decode table width each) and the table widths that contexts grow into,
and for an open-loop mix runs the ramp.  The window then opens.  A mix
whose arrivals are ``poisson`` keeps arriving after the window has closed
until every request that was due inside it has finished.
"""

from __future__ import annotations

import functools
import sys
import time

import numpy as np

from .. import loadgen, reference, weights
from . import llama_config


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _warm_lengths(traffic: dict, block_size: int) -> list[int]:
    """The mix's own prompt lengths, and one more prompt for every decode
    table width (a power of two of blocks) that a context can grow into
    and none of them starts at."""
    lens = loadgen.warm_prompt_lengths(traffic)
    blocks = lambda ctx: -(-(ctx + 1) // block_size)
    top = max(lens) + max(loadgen.levels(traffic["output"]))
    have = {_pow2(blocks(p)) for p in lens}
    w = _pow2(blocks(min(lens)))
    extra = []
    while w <= _pow2(blocks(top)):
        if w not in have:
            extra.append(max((w // 2) * block_size + 1, 1))
        w *= 2
    return lens + extra


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values (inf counts as a miss)."""
    v = np.sort(np.asarray(values, float))
    return float(v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)])


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.models import llama
    from horovod_tpu.obs import REGISTRY

    cfg, tr = run.config, run.traffic
    dims = reference.dims_of(cfg)
    dtype_name = cfg["torch_dtype"]
    dtype = jnp.dtype(dtype_name)
    lcfg = llama_config(cfg, dims, dtype)
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
    occupancy = REGISTRY.get("hvd_serving_batch_occupancy")

    arrivals = tr["arrivals"]
    ramp = float(arrivals.get("ramp_s", 0.0))
    open_loop = arrivals["kind"] != "backlog"
    reqs = loadgen.schedule(tr, run.seed, dims["vocab_size"],
                            run.seconds + float(arrivals.get("tail_s", 0)))

    # -- warm-up ---------------------------------------------------------
    wrng = loadgen.rng_for(run.seed, 7)
    for p in _warm_lengths(tr, eng["block_size"]):
        session.submit(wrng.integers(0, dims["vocab_size"], size=p,
                                     dtype=np.int32), 2)
        session.drain()

    run.mark("warm")

    # -- the loop --------------------------------------------------------
    recs = [dict(due=r["due_s"], prompt=r["prompt"],
                 prompt_len=len(r["prompt"]),
                 max_tokens=r["max_tokens"], t_submit=None, t_first=None,
                 t_last=None, streamed=[], fut=None) for r in reqs]
    emits: list = []                       # (index, is_first) this step
    steps: list = []                       # per engine turn

    def on_token(i, _req_id, token):
        rec = recs[i]
        now = time.perf_counter()
        if rec["t_first"] is None:
            rec["t_first"] = now
        rec["t_last"] = now
        emits.append((i, len(rec["streamed"])))
        rec["streamed"].append(int(token))

    def measured(rec):
        return 0.0 <= rec["due"] < run.seconds

    t_base = time.perf_counter() + ramp
    nxt = 0
    opened = closed = False
    t_close = None
    while True:
        now = time.perf_counter()
        rel = now - t_base
        if not opened and rel >= 0:
            t_base = run.open_window()     # the clock the window uses
            opened, rel = True, 0.0
        if opened and not closed:
            now = run.poll()
            rel = now - t_base
            if rel >= run.seconds:
                run.close_window()
                closed, t_close = True, now
        if closed and (not open_loop or all(
                r["fut"] is not None and r["fut"].done()
                for r in recs if measured(r))):
            break
        while nxt < len(reqs) and reqs[nxt]["due_s"] <= rel:
            with run.span("submit"):
                recs[nxt]["t_submit"] = time.perf_counter()
                recs[nxt]["fut"] = session.submit(
                    reqs[nxt]["prompt"], reqs[nxt]["max_tokens"],
                    stream_cb=functools.partial(on_token, nxt))
            nxt += 1
        if session.engine.has_work():
            t0 = time.perf_counter()
            with run.span("engine.step"):
                session.drain(max_steps=1)
            t1 = time.perf_counter()
            steps.append(dict(
                t0=t0, t1=t1, occupancy=occupancy.value,
                prefill=[recs[i]["prompt_len"] for i, k in emits if k == 0],
                decode=[recs[i]["prompt_len"] + k for i, k in emits if k]))
            emits.clear()
        else:
            with run.span("loadgen.wait"):
                due = t_base + reqs[nxt]["due_s"] if nxt < len(reqs) \
                    else now + 0.001
                time.sleep(max(0.0, min(due - time.perf_counter(), 0.001)))
    t_open = t_base
    wall = t_close - t_open

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
    tokens = 0
    for st in steps:
        if in_win(st["t1"]):
            tokens += sum(st["prefill"]) + len(st["prefill"]) + \
                len(st["decode"])
    end_to_end, counters = {}, {}
    if open_loop:
        pool = [r for r in recs if measured(r)]
        ok = [r for r in pool if served(r)]
        ttft = [(r["t_first"] - (t_open + r["due"])) * 1e3
                if served(r) else np.inf for r in pool]
        tpot = [(r["t_last"] - r["t_first"]) * 1e3 /
                max(len(r["streamed"]) - 1, 1) if served(r) else np.inf
                for r in pool]
        end_to_end = {"ttft_ms_p90": percentile(ttft, 90),
                      "tpot_ms_p90": percentile(tpot, 90)}
        counters["queue_wait_ms"] = [
            (r["result"].metrics["queue_wait_s"]
             + r["t_submit"] - (t_open + r["due"])) * 1e3 for r in ok]
        counters["loadgen_late_ms"] = [
            (r["t_submit"] - (t_open + r["due"])) * 1e3 for r in pool
            if r["t_submit"] is not None]
        attempted, failed = len(pool), len(pool) - len(ok)

        def in_system(t):
            """Requests due by ``t`` and not finished by then."""
            return sum(1 for r in recs if t_open + r["due"] <= t and (
                r["t_last"] is None or r["t_last"] > t
                or len(r["streamed"]) < r["max_tokens"]))
        counters["in_system_at_open"] = in_system(t_open)
        counters["in_system_at_close"] = in_system(t_close)
    else:
        pool = [r for r in done if in_win(r["t_last"])]
        ok = [r for r in pool if served(r)]
        end_to_end = {"serve_tokens_per_s": tokens / wall}
        attempted, failed = len(pool), len(pool) - len(ok)
    counters.update(
        steps=steps, t_open=t_open, t_close=t_close, window_wall_s=wall,
        block_size=eng["block_size"],
        max_active=eng["max_active"], tokens_in_window=tokens)

    # -- the sample the reference is run over ----------------------------
    good = [r for r in recs if served(r)]
    k = min(int(tr["check_requests"]), len(good))
    longest = max(good, key=lambda r: r["prompt_len"] + len(r["streamed"]),
                  default=None)
    others = [r for r in good if r is not longest]
    pick = loadgen.rng_for(run.seed, 6).permutation(len(others))[:max(k - 1, 0)]
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
        rows.append(("served_gap_max", float(gaps["served_gap"].max()),
                     lim["served_gap_max"]))
        if control:
            c = gaps["control_gap"]
            run.controls["fp8"] = {
                "served_gap_max": float(c.max()),
                "flipped": int((c > 0).sum()), "tokens": gaps["n_tokens"]}
            print(f"[chipbench] control fp8: {run.controls['fp8']}; "
                  f"program flipped {(gaps['served_gap'] > 0).sum()}",
                  file=sys.stderr, flush=True)
        return rows

    n_ticks = sum(1 for s in steps if s["decode"] and in_win(s["t1"]))
    n_pre = sum(len(s["prefill"]) for s in steps if in_win(s["t1"]))
    return {
        "attempted": attempted, "failed": failed + bad,
        "end_to_end": end_to_end, "counters": counters,
        "notes": [f"window {wall:.3f} s: {n_pre} prefills, {n_ticks} decode "
                  f"ticks, {tokens} tokens, {len(pool)} requests judged, "
                  f"{len(recs) - len(done)} still queued or running at the "
                  f"end; {len(samples)} requests "
                  f"({sum(len(t) for _, t in samples)} served tokens) go to "
                  "the reference",
                  lambda: f"the reference took "
                  f"{run.counters.get('reference_s', 0):.1f} s" + (
                      f"; in the system at the window's open and close: "
                      f"{counters['in_system_at_open']}, "
                      f"{counters['in_system_at_close']}"
                      if open_loop else "")],
        "release": release, "check": check,
    }
