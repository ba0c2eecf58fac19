"""The serving driver over modules the configuration names: the loop of
:mod:`chipbench.drivers.serve_ouro` (a backlog through ``serving.serve``,
a record a turn, the longest and a few other served requests to the
reference) with nothing bound by import.  The configuration file's
``modules`` gives the program's module and the name of its configuration
class (built by its ``from_published`` from the file's published keys),
and the benchmark's ``weights``, ``reference`` and ``costs`` modules.
This is the file ``serve.py`` and ``serve_ouro.py`` can fold into, which
is a benchmark issue's to do (PERF.md, section 7): both are bound to
their three names by import and are not this PR's to edit.

What the named modules give: ``weights.dims_of(config)``,
``weights.root_key(seed)``, ``weights.stacked(key, dims, dtype)``;
``reference.served_gaps(seed, dims, dtype_name, samples, control=,
picks=)`` returning ``served_gap``, ``n_tokens``, with ``control`` a
``control_gap``, and ``checks``, further ``(name, value, name of its
limit in the traffic file)`` rows.  Of the served gaps a run is held to
the statistics its traffic file's ``limits`` name (``served_gap_max``,
``served_gap_mean``).  Where the program's ``forward`` can
return its expert picks (``modules.picks``), the driver runs it over the
checked requests, on weights made again from the seed after the server's
state is gone, and hands the picks to the reference.

``serve_tokens_per_s`` is the tokens of the turns made in the window
(prompt tokens of first prefills, every output token) over
``--seconds``, the turn that straddles the window's end counted by the
share of its time that lies before it.  A turn with a prefill of 12,000
tokens is 1% of a window's tokens in 0.4% of its time; counted whole or
not at all, the rate steps by 0.7% with a few milliseconds' difference in
where the window's end falls (six runs read 23,463 three times and 23,300
twice: 170 prefills or 169; PERF.md, section 6), which is half the
metric's bound.

Shapes a preempted request's second prefill can have are warmed only
where the traffic file says the mix preempts (``warm_resumed``): there
is one scatter program a (bucket, block count), hundreds at contexts of
13k tokens, and a mix whose 64 slots fill the pool to three quarters
never makes one.  The run's notes and counters say how many preemptions
the window saw.
"""

from __future__ import annotations

import collections
import functools
import importlib
import sys
import time

import numpy as np

from .. import loadgen
from .serve import _warm_lengths
from .serve_ouro import _resumed_lengths


def load_modules(cfg: dict, dtype):
    """``(program config, weights, reference)`` of the configuration
    file; exits cleanly where this checkout's program lacks the model."""
    mods = cfg["modules"]
    weights = importlib.import_module("chipbench." + mods["weights"])
    reference = importlib.import_module("chipbench." + mods["reference"])
    try:
        model = importlib.import_module(mods["program"])
        pcfg = getattr(model, mods["program_config"]).from_published(
            cfg, dtype=dtype)
    except (ImportError, AttributeError) as e:
        raise SystemExit(f"chipbench: this checkout's program cannot state "
                         f"the configuration ({e}); nothing ran") from None
    return model, pcfg, weights, reference


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.obs import REGISTRY

    cfg, tr = run.config, run.traffic
    dtype_name = cfg["torch_dtype"]
    dtype = jnp.dtype(dtype_name)
    model, pcfg, weights, reference = load_modules(cfg, dtype)
    dims = weights.dims_of(cfg)
    eng = dict(cfg["engine"])
    eng["prefill_buckets"] = tuple(eng.get("prefill_buckets", ()))
    key = weights.root_key(run.seed)
    make_params = jax.jit(lambda k: weights.stacked(k, dims, dtype))
    params = make_params(key)
    jax.block_until_ready(params)
    run.mark("weights")
    try:
        session = serving.serve(params, pcfg, **eng)
    except NotImplementedError as e:
        raise SystemExit(f"chipbench: this checkout's server refuses the "
                         f"configuration ({e}); nothing ran") from None
    want = cfg.get("attention_path")
    got = session.engine.attention_path
    assert want in (None, got), f"decode attention path {got!r}, not {want!r}"
    assert session.engine.cache.n_layers == dims["n_layers"]
    if "prefill_path" in cfg:
        for b in eng["prefill_buckets"]:
            path = model.prefill_path(pcfg, b)
            assert path == cfg["prefill_path"], (b, path)
    occupancy = REGISTRY.get("hvd_serving_batch_occupancy")
    preemptions = REGISTRY.get("hvd_serving_preemptions_total")

    arrivals = tr["arrivals"]
    assert arrivals["kind"] == "backlog", "serve_named drives backlog mixes"
    reqs = loadgen.schedule(tr, run.seed, dims["vocab_size"], run.seconds)

    # -- warm-up ---------------------------------------------------------
    wrng = loadgen.rng_for(run.seed, 7)
    lens = _warm_lengths(tr, eng["block_size"])
    if tr.get("warm_resumed", True):
        lens = lens + _resumed_lengths(tr, eng, lens)
    else:
        # the one prefill program only a resumed request has (its bucket)
        top = max(loadgen.levels(tr["prompt"])) + \
            max(loadgen.levels(tr["output"])) - 1
        lens = lens + [top]
    for p in lens:
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
    # resumed prefill's tokens were counted when they were first made;
    # the turn that straddles the window's end by its share before it
    deadline = t_open + run.seconds
    before = lambda st: min(1.0, max(0.0, (deadline - st["t0"])
                                     / (st["t1"] - st["t0"])))
    tokens = sum(before(st) * (sum(st["prefill"]) + len(st["prefill"])
                               + len(st["resumed"]) + len(st["decode"]))
                 for st in steps)
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

    def program_picks():
        """The program's expert picks on each sample's tokens: its
        ``forward`` (whole sequence, no cache), on weights made again."""
        seqs = [np.concatenate([np.asarray(p, np.int32),
                                np.asarray(t, np.int32)])
                for p, t in samples]
        pad = int(cfg["modules"]["picks_pad"])
        S = -(-max(len(s) for s in seqs) // pad) * pad
        p = make_params(key)
        fwd = jax.jit(lambda p, tok: model.forward(
            p, tok, pcfg, return_hidden=True, picks=True)[1]["experts"])
        out = []
        for s in seqs:
            tok = np.zeros((1, S), np.int32)
            tok[0, :len(s)] = s
            out.append(np.asarray(fwd(p, jnp.asarray(tok))))
        return out

    def check(control: bool):
        lim = tr["limits"]
        rows = [("requests_failed", float(failed + bad), 0.0),
                ("answers_altered", float(wrong), 0.0)]
        gap_rows = lambda g: [
            (name, float(stat(g)), lim[name]) for name, stat in (
                ("served_gap_max", np.max), ("served_gap_mean", np.mean))
            if name in lim]
        if not samples:
            return rows + gap_rows(np.asarray([np.inf]))
        t0 = time.perf_counter()
        picks = program_picks() if cfg["modules"].get("picks_pad") else None
        gaps = reference.served_gaps(run.seed, dims, dtype_name, samples,
                                     control=control, picks=picks)
        run.counters["reference_s"] = time.perf_counter() - t0
        run.counters["checked_tokens"] = gaps["n_tokens"]
        rows += gap_rows(gaps["served_gap"])
        rows += [(n, float(v), lim[k]) for n, v, k in gaps.get("checks", [])]
        g = gaps["served_gap"]
        print(f"[chipbench] served gaps: max {g.max():.6g} mean "
              f"{g.mean():.6g} p99 {np.quantile(g, 0.99):.6g} flipped "
              f"{(g > 0).sum()} of {g.size}; picks against the "
              f"reference's: {gaps.get('picks')}", file=sys.stderr,
              flush=True)
        if control:
            c = gaps["control_gap"]
            run.controls["fp8"] = {
                "served_gap_max": float(c.max()),
                "served_gap_mean": float(c.mean()),
                "flipped": int((c > 0).sum()), "tokens": gaps["n_tokens"],
                **{"picks_" + k: v for k, v in
                   gaps.get("control_picks", {}).items()}}
            print(f"[chipbench] control fp8: {run.controls['fp8']}; "
                  f"program flipped {(gaps['served_gap'] > 0).sum()}",
                  file=sys.stderr, flush=True)
        return rows

    n_ticks = sum(1 for s in inside if s["decode"])
    n_pre = sum(len(s["prefill"]) for s in inside)
    n_again = sum(len(s["resumed"]) for s in inside)
    return {
        "attempted": attempted, "failed": failed + bad,
        "end_to_end": {"serve_tokens_per_s": tokens / run.seconds},
        "counters": counters,
        "notes": [f"window {wall:.3f} s: {n_pre} prefills, {n_again} "
                  f"resumed prefills of {again} tokens after {preempted} "
                  f"preemptions, {n_ticks} decode ticks, {tokens:.0f} tokens "
                  f"before {run.seconds:g} s, "
                  f"{len(pool)} requests judged, {len(recs) - len(done)} "
                  f"still queued or running at the end; {len(samples)} "
                  f"requests ({sum(len(t) for _, t in samples)} served "
                  "tokens) go to the reference",
                  lambda: f"the reference took "
                  f"{run.counters.get('reference_s', 0):.1f} s"],
        "release": release, "check": check,
    }
