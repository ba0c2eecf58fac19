"""From the profiler's xplane file to busy, idle, gaps and op times.

Reads the file with nothing but JAX (``jax.profiler.ProfileData``).  A
device plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
per executed op, its ``XLA Modules`` line one per executed program.  The
benchmark's own spans (``chipbench.*`` TraceAnnotations) are host events on
the same clock, so an idle gap on the device can be named by what the host
was doing in it.
"""

from __future__ import annotations

import glob
import json
import os
import re

SPAN_PREFIX = "chipbench."
MOSAIC = 'custom_call_target="tpu_custom_call"'
# Ops that only hold other ops: their time is their children's.
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
_SHAPE = re.compile(r"\b(?:bf16|f16|f32|s32|u32|s8|u8|pred|f8\w*)\[[\d,]*\]")


def short_name(name: str) -> str:
    """The trace names a device op by its whole HLO line; keep the op's
    own name, its opcode, the shapes it yields, and ``mosaic`` for a
    Pallas kernel."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:120]
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rhs)   # layouts are T( S(
    out = rhs[:m.start()] if m else rhs[:60]
    return " ".join(filter(None, [
        lhs.lstrip("%"), m.group(1) if m else "",
        ",".join(_SHAPE.findall(out)) or out.strip()[:40],
        "mosaic" if MOSAIC in name else ""]))[:160]


def read_xplane(path: str) -> dict:
    """Events of one trace: ``ops`` and ``modules`` as (device, name,
    start_s, dur_s), ``spans`` as (name, start_s, dur_s)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, spans, lines_seen = [], [], [], []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            lines_seen.append(f"{plane.name}|{line.name}")
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                dev = int(plane.name.rsplit(":", 1)[1])
                dst = ops if line.name == "XLA Ops" else modules
                for e in line.events:
                    dst.append((dev, e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9))
            elif not is_dev:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    return {"ops": ops, "modules": modules, "spans": spans,
            "lines": lines_seen}


def union_length(intervals, lo: float, hi: float):
    """Length of the union of (start, end) clipped to [lo, hi], and the
    gaps between its pieces as (start, end)."""
    busy, gaps, at = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            gaps.append((at, s))
        if e > at:
            busy += e - max(s, at)
            at = e
    if hi > at:
        gaps.append((at, hi))
    return busy, gaps


def name_gaps(gaps, spans) -> dict:
    """Seconds of idle by the innermost (shortest) span that covers each
    gap's midpoint; ``(no span)`` where the host was in none."""
    out: dict = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(d, n) for n, t, d in spans if t <= mid <= t + d]
        name = min(cover)[1] if cover else "(no span)"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def reduce_events(ev: dict) -> dict:
    """Busy seconds (mean over devices), the window, the ten heaviest ops
    and the idle by host span."""
    ops, spans = ev["ops"], ev["spans"]
    if not ops:
        raise SystemExit("chipbench: the trace holds no device operation")
    if spans:
        lo = min(t for _, t, _ in spans)
        hi = max(t + d for _, t, d in spans)
    else:
        lo = min(t for _, _, t, _ in ops)
        hi = max(t + d for _, _, t, d in ops)
    devs = sorted({o[0] for o in ops})
    busy, gaps0 = [], []
    for dev in devs:
        b, gaps = union_length(
            [(t, t + d) for dv, _, t, d in ops if dv == dev], lo, hi)
        busy.append(b)
        if dev == devs[0]:
            gaps0 = gaps
    by_op: dict = {}
    for dv, n, t, d in ops:
        if lo <= t <= hi and not _CONTAINER.match(n):
            n = short_name(n)
            by_op[n] = by_op.get(n, 0.0) + d / len(devs)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(name_gaps(gaps0, spans).items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy), "window_s": hi - lo,
        "lo": lo, "hi": hi, "devices": devs,
        "breakdown": {"device_ops": [[n, s] for n, s in top],
                      "idle_gaps": [[n, s] for n, s in idle[:10]]},
    }


def reduce_run(run) -> dict:
    """What ``run.py`` needs of a traced run, with the events kept for the
    per-layer reducers."""
    paths = glob.glob(os.path.join(run.trace_dir, "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not paths:
        raise SystemExit("chipbench: the profiler wrote no xplane file")
    ev = read_xplane(max(paths, key=os.path.getmtime))
    red = reduce_events(ev)
    red.update(ev)
    mods: dict = {}
    for _, n, _, d in ev["modules"]:
        c = mods.setdefault(n.split("(")[0], [0, 0.0])
        c[0] += 1
        c[1] += d
    by_op: dict = {}
    for _, n, _, d in ev["ops"]:
        c = by_op.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += d
    with open(os.path.join(run.out_dir, "trace_summary.json"), "w") as f:
        json.dump({"lines": sorted(set(ev["lines"])), "programs": mods,
                   "spans": sorted({n for n, _, _ in ev["spans"]}),
                   "ops": sorted(by_op.items(),
                                 key=lambda kv: -kv[1][1])[:80]}, f, indent=1)
    red["notes"] = [
        f"trace: {len(ev['ops'])} device ops, {len(ev['modules'])} program "
        f"runs, {len(ev['spans'])} host spans over {red['window_s']:.3f} s; "
        f"busy {red['busy_s']:.3f} s",
        "trace programs: " + ", ".join(
            f"{n} x{c} {s:.3f}s" for n, (c, s) in sorted(
                mods.items(), key=lambda kv: -kv[1][1])[:8]),
    ]
    return red


# -- helpers for reducers -----------------------------------------------------

def op_seconds(red: dict, patterns, module_patterns=None) -> tuple:
    """Total seconds (mean over devices) and count of ops whose name (the
    whole HLO line) matches any of the regular expressions ``patterns``;
    with ``module_patterns`` only ops that ran inside a program whose name
    holds one of them."""
    patterns = [re.compile(p) for p in patterns]
    lo, hi, n_dev = red["lo"], red["hi"], len(red["devices"])
    spans_by_dev: dict = {}
    if module_patterns:
        for dv, t, d in module_runs(red, module_patterns):
            spans_by_dev.setdefault(dv, []).append((t, t + d))
    total, count = 0.0, 0
    for dv, n, t, d in red["ops"]:
        if not (lo <= t <= hi) or not any(p.search(n) for p in patterns):
            continue
        if module_patterns and not any(
                s <= t <= e for s, e in spans_by_dev.get(dv, ())):
            continue
        total += d
        count += 1
    return total / n_dev, count / n_dev


def module_runs(red: dict, spec) -> list:
    """(device, start, dur) of the program runs a metric's ``programs``
    argument picks: a list of substrings of the program's name, or a dict
    ``{"names": [...], "with_op": regex, "without_op": regex, "min_ms":
    x}`` for programs that share a name (the serving engine's jitted
    partials are all ``jit__unknown``) and differ in what runs inside."""
    if isinstance(spec, list):
        spec = {"names": spec}
    runs = [(dv, t, d) for dv, n, t, d in red["modules"]
            if red["lo"] <= t <= red["hi"]
            and any(p in n for p in spec["names"])
            and d * 1e3 >= spec.get("min_ms", 0.0)]
    for key, want in (("with_op", True), ("without_op", False)):
        if key in spec:
            pat = re.compile(spec[key])
            hits = [(dv, t) for dv, n, t, _ in red["ops"] if pat.search(n)]
            runs = [(dv, t, d) for dv, t, d in runs
                    if any(dv == hv and t <= ht <= t + d
                           for hv, ht in hits) == want]
    return runs
