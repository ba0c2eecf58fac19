"""The program's own profiler spans (``hvd.*``), beside the device ops.

``horovod_tpu.obs.trace.profiler_span`` writes TraceAnnotations named
``hvd.<layer>.<what>`` into the profiler's trace, with integer attributes
(``tokens``, ``blocks``, ...).  ``trace.py`` keeps only the benchmark's
``chipbench.*`` spans, so this file reads the others from the same xplane
file, once a run (kept in the run's ``red``), and shares out the device's
idle time among them.
A program that has no such spans (an older commit) yields an empty list
and every reducer built on it returns None.

The profiler puts host and device events on one clock, but only to a
millisecond or two: in four traces of one cell out of eight, every program
started 0.1 to 1.5 ms before the host had opened the span that dispatches
it (my chip runs, PR 27).  A turn's idle is 3 to 4 ms, so such a trace
shares it out wrongly, and two traces of the same work differently.  What
cannot be wrong is the order of cause and effect: a program starts after
the span that dispatches it has opened, and ends before the span that
fetches its result has closed.  A metric whose reading depends on where
the spans lie names such triples in its spec (``args.causal``: program,
dispatching span, fetching span).  They leave the host's clock about a
millisecond of room against the device's.  The trace's spans are moved to
the middle of that room, where the tightest launch and the tightest fetch
have the same slack, so that two traces are read alike; the shift and the
room are reported, and half the room is how far a reading that a span's
edge decides may be off.
"""

from __future__ import annotations

import glob
import json
import math
import os
import sys

from . import trace

PREFIX = "hvd."
NO_SPAN = "(no span)"
#: a run and a span farther apart than this are not each other's
NEAR_S = 0.010
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")


def read_spans(path: str) -> list:
    """``(name, start_s, dur_s, attrs)`` of every ``hvd.*`` host event in
    an xplane file, on the clock ``trace.read_xplane`` puts the device ops
    on, in order of start."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9, dict(e.stats)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def clock_shift(red: dict, spans, causal):
    """``(shift, lo, hi)``: the seconds to add to every span's start.  No
    program of ``causal`` (triples of program, dispatching span, fetching
    span) starts before its dispatch span opens (``shift <= hi``) or ends
    after its fetch span closes (``lo <= shift``); the shift is the
    middle of the two.  With one bound alone, 0 if that allows it and
    else the bound; with none, 0.  None where the two contradict each
    other (``lo > hi``): no shift puts cause before effect."""
    dev = red["devices"][0]
    nearest = lambda t, ts: min((abs(t - x), t - x) for x in ts)[1]
    lo, hi = float("-inf"), float("inf")
    for program, dispatch, fetch in causal:
        opens = [t for n, t, _, _ in spans if n == dispatch]
        closes = [t + d for n, t, d, _ in spans if n == fetch]
        for dv, t, d in trace.module_runs(red, [program]):
            if dv != dev:
                continue
            early = nearest(t, opens) if opens else NEAR_S
            late = nearest(t + d, closes) if closes else NEAR_S
            if abs(early) < NEAR_S:            # < 0: started too soon
                hi = min(hi, early)
            if abs(late) < NEAR_S:             # > 0: ended too late
                lo = max(lo, late)
    if lo > hi:
        return None
    if lo > float("-inf") and hi < float("inf"):
        return 0.5 * (lo + hi), lo, hi
    return min(max(0.0, lo), hi), lo, hi


def placed(red: dict, cell: dict) -> dict:
    """``{"spans", "clock"}`` of this cell's traced run: the ``hvd.*``
    spans of the newest xplane file under ``out/<cell>/trace``, the one
    ``trace.reduce_run`` picks, moved by the ``clock_shift`` that the
    metric's ``args.causal`` gives (none: left where they are).  Where
    cause and effect contradict each other, ``clock`` is None, the spans
    stay where the profiler put them, and that is logged."""
    if "hvd_spans" not in red:
        paths = glob.glob(os.path.join(
            OUT, cell["cell"]["name"], "trace", "plugins", "profile", "*",
            "*.xplane.pb"))
        red["hvd_spans"] = read_spans(
            max(paths, key=os.path.getmtime)) if paths else []
    causal = cell["spec"]["args"].get("causal", [])
    key = json.dumps(causal)
    by_causal = red.setdefault("hvd_placed", {})
    if key not in by_causal:
        clock = clock_shift(red, red["hvd_spans"], causal)
        if clock is None:
            print(f"[chipbench] {cell['cell']['name']}: no clock shift puts "
                  f"every program of {causal} after its dispatch span's "
                  "opening and before its fetch span's close; the hvd.* "
                  "spans stay where the profiler put them",
                  file=sys.stderr, flush=True)
        shift = clock[0] if clock else 0.0
        by_causal[key] = {"clock": clock, "spans": [
            (n, t + shift, d, a) for n, t, d, a in red["hvd_spans"]]}
    return by_causal[key]


def spans_of(red: dict, cell: dict) -> list:
    """``placed(red, cell)["spans"]``."""
    return placed(red, cell)["spans"]


def innermost(spans) -> list:
    """Disjoint ``(start, end, name)`` pieces, in order: at each instant
    that any span covers, the name of the innermost (shortest) one."""
    edges = sorted(
        edge for i, (_, s, d, _) in enumerate(spans) if d > 0
        for edge in ((s, 1, i), (s + d, 0, i)))      # at a tie, close first
    open_, out, at = set(), [], None
    for t, opens, i in edges:
        if open_ and t > at:
            out.append((at, t, min(
                (spans[j][2], spans[j][0]) for j in open_)[1]))
        (open_.add if opens else open_.discard)(i)
        at = t
    return out


def idle_pieces(gaps, spans):
    """``(name, seconds)`` of every stretch of the idle intervals ``gaps``
    (sorted, disjoint (start, end)) that lies under one span's own time:
    every instant of a gap goes to the innermost span that covers it, a
    gap that straddles spans is split by overlap, and what no span covers
    comes as ``(no span)``, once a gap."""
    pieces = innermost(spans)
    k = 0
    for s, e in gaps:
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        covered, j = 0.0, k
        while j < len(pieces) and pieces[j][0] < e:
            lo, hi, name = pieces[j]
            yield name, min(hi, e) - max(lo, s)
            covered += min(hi, e) - max(lo, s)
            j += 1
        if e - s > covered:
            yield NO_SPAN, (e - s) - covered


def idle_by_span(gaps, spans) -> dict:
    """Seconds of idle under each span's own time (``idle_pieces``,
    summed by name)."""
    out: dict = {}
    for name, seconds in idle_pieces(gaps, spans):
        out[name] = out.get(name, 0.0) + seconds
    return out


def idle_partition(red: dict, cell: dict):
    """``idle_by_span`` of the first device's idle intervals in this
    cell's traced run (as ``trace.reduce_events`` takes them), or None
    where the program wrote no ``hvd.*`` span.  The first call writes the
    whole partition to ``out/<cell>/program_spans.json`` and logs it."""
    at = placed(red, cell)
    if "idle" in at:
        return at["idle"]
    if not at["spans"]:
        at["idle"] = None
        return None
    dev = red["devices"][0]
    _, gaps = trace.union_length(
        [(t, t + d) for dv, _, t, d in red["ops"] if dv == dev],
        red["lo"], red["hi"])
    part = at["idle"] = idle_by_span(gaps, at["spans"])
    idle = red["window_s"] - red["busy_s"]
    ranked = sorted(part.items(), key=lambda kv: -kv[1])
    clock = at["clock"] and dict(zip(
        ("applied", "at_least", "at_most"),
        (x if math.isfinite(x) else None for x in at["clock"])))
    with open(os.path.join(OUT, cell["cell"]["name"],
                           "program_spans.json"), "w") as f:
        json.dump({"window_s": red["window_s"], "busy_s": red["busy_s"],
                   "idle_s": idle, "idle_by_span_s": dict(ranked),
                   "partition_sum_s": sum(part.values()),
                   "clock_shift_s": clock}, f, indent=1)
    print("[chipbench] idle by program span: "
          + ", ".join(f"{n} {s:.4f}" for n, s in ranked)
          + f"; sum {sum(part.values()):.4f} s of {idle:.4f} s idle; "
          + ("spans moved by {:.3f} ms (cause and effect allow {:.3f} to "
             "{:.3f})".format(*(1e3 * x for x in at["clock"]))
             if at["clock"] else "spans not moved"),
          file=sys.stderr, flush=True)
    return part
