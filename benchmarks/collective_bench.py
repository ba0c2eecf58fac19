"""Collective microbenchmarks: allreduce/allgather/alltoall bus bandwidth.

The BASELINE metric: "allreduce bus bandwidth >= 90% of ICI peak on
v5p-64".  Bus bandwidth uses the standard (NCCL-tests) accounting — for a
ring allreduce each device moves ``2*(N-1)/N * bytes`` on the wire
(† ``docs/concepts.rst`` ring cost model), so

    busbw = (2*(N-1)/N) * payload_bytes / time        (allreduce)
    busbw = ((N-1)/N)   * payload_bytes / time        (allgather/alltoall/rs)

Run directly (``python -m benchmarks.collective_bench``) for a sweep table,
or call :func:`allreduce_busbw` for one point.  On a single chip there is
no inter-chip wire; the sweep still validates dispatch overhead and HBM
throughput, and the same harness scales to any mesh.

Wire precision (``--wire-precision fp32,bf16,int8,...``): sweeps the
engine's wire modes (ops/reduction.py) and reports per mode

- ``dispatch_GBs`` / ``busbw_GBs`` — measured wall-clock on the LOGICAL
  payload (what the caller's gradients experience);
- ``wire_reduction`` — analytic interconnect bytes saved vs the fp32
  ring (``reduction.ring_wire_bytes``), the number that transfers to a
  bandwidth-bound interconnect (int8 ≈ 2.6x at the default block).

Read both columns together: on TPU wire time dominates so
``wire_reduction`` converts to wall-clock (EQuARX measures ~2x); the CPU
rig's collectives are shared-memory and byte-width-insensitive while its
8x-oversubscribed cores inflate the quantize arithmetic, so wall-clock
there does NOT improve — see docs/performance.md "Wire precision".

Schedule (``--schedule monolithic,rs_ag:2,rs_ag:4,...``): sweeps the
collective schedule (ops/sched) and reports per row

- ``dispatch_GBs`` — measured wall-clock (monolithic psum vs the chunked
  reduce-scatter/allgather pipeline);
- ``overlap_window`` — the analytic fraction of communication the
  schedule *exposes* for overlap, ``(k-1)/k`` at k chunks (chunk c's
  comm can hide under the other chunks' compute);
- ``overlap_fraction`` — the executor's measured in-flight overlap
  gauge for the run (host dispatch windows).

Same caveat pattern as wire precision: the CPU rig serializes device
work, so decomposed wall-clock there is dispatch-overhead-bound and does
NOT improve; ``overlap_window`` is the number that transfers to a TPU
whose async collectives fill it.  ``--out`` writes the schedule sweep as
a BENCH_rXX.json-style record.

Hierarchy (``--hierarchy``): treats the mesh as two tiers (np=4 as 2x2
by default, split from ``HVDTPU_HIERARCHICAL_LOCAL_SIZE`` or config)
and sweeps flat vs the tiered monolithic kernel (ops/hierarchical.py)
vs the chunked+tiered schedule (``hier:<n_local>:2``) with every wire
mode on the cross hop.  Hier rows report ``local_wire_bytes`` /
``cross_wire_bytes`` (analytic, obs/perfmodel.expected_hierarchical)
and ``cross_wire_reduction`` vs the flat fp32 ring.

The honest CPU-rig caveat, sharpened for this sweep: the rig's "DCN"
is the same shared memory as its "ICI", so the defining two-tier win —
the slow cross fabric carrying only ``1/n_local`` of the payload —
CANNOT appear in wall-clock here (the tiered path just runs three
collectives instead of one and measures slower).  The number that
transfers to a real ICI/DCN pod is ``cross_wire_reduction``:
``n_local x`` at fp32, ``~2.6 * n_local x`` with an int8 cross hop
(EQuARX-style), asserted analytically per row.
"""

from __future__ import annotations

import json
import time
from typing import Optional

import numpy as np


def _fence(x) -> None:
    # device->host readback of one element: timing waits for the
    # computation, not for its enqueue.
    np.asarray(jax_device_get_first(x))


def jax_device_get_first(x):
    import jax
    return jax.device_get(x.ravel()[0] if hasattr(x, "ravel") else x)


def allreduce_busbw(nbytes: int, *, iters: int = 20, warmup: int = 3,
                    dtype="float32", wire_precision: str = "fp32",
                    schedule: str = "monolithic",
                    fence_each: bool = False) -> dict:
    """One allreduce bandwidth point on the current global mesh."""
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.ops import reduction as R

    n = hvd.size()
    itemsize = np.dtype(dtype).itemsize
    numel = max(1, nbytes // itemsize)
    x = hvd.per_rank_from_fn(
        lambda r: np.full((numel,), float(r + 1), dtype))
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops import sched as S
    cfg = hvd.global_state().config
    # Report what actually runs: the resolver may downgrade (size floor,
    # single-rank mesh, ...) — a row must never claim quantized savings
    # for an allreduce that executed at fp32, nor overlap for one that
    # ran monolithic.
    resolved = R.resolve_precision(wire_precision, hvd.Sum, np.dtype(dtype),
                                   nbytes, cfg, n)
    resolved_sched = S.resolve_schedule(schedule, "allreduce", hvd.Sum,
                                        np.dtype(dtype), nbytes, cfg, n,
                                        resolved)

    def one():
        return C.allreduce(x, hvd.Sum, precision=wire_precision,
                           schedule=schedule)

    out = one()
    _fence(out)
    for _ in range(warmup):
        out = one()
        if fence_each:
            _fence(out)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = one()
        if fence_each:
            # The tiered paths launch several sub-programs per call;
            # letting 20 of those pipeline unfenced starves XLA:CPU's
            # cross_module rendezvous threads into deadlock.  Fencing
            # each iteration caps in-flight work at one execution — it
            # adds a readback per iter, which the rig absorbs (its
            # numbers are dispatch-bound either way; module docstring).
            _fence(out)
    _fence(out)
    dt = (time.perf_counter() - t0) / iters
    payload = numel * itemsize
    algbw = payload / dt
    row = {"op": "allreduce", "bytes": payload, "time_us": dt * 1e6,
           "algbw_GBs": algbw / 1e9, "ranks": n,
           "wire_precision": resolved}
    if resolved != wire_precision:
        row["requested_precision"] = wire_precision
    if schedule != "monolithic":
        row["schedule"] = resolved_sched or "monolithic"
        if resolved_sched:
            from horovod_tpu.ops.sched import executor as SE
            hier = S.parse_hier_descriptor(resolved_sched)
            comp = S.parse_compiled_descriptor(resolved_sched)
            kreq = hier[1] if hier else (
                comp if comp is not None
                else S.parse_descriptor(resolved_sched))
            cross_mode = (SE.resolve_cross_mode(resolved, cfg)
                          if hier else "")
            mode_eff = resolved if resolved in R.QUANT_MODES else \
                (cross_mode if cross_mode in R.QUANT_MODES else resolved)
            k = len(S.chunk_layout(numel, n, kreq, mode_eff,
                                   cfg.quant_block_size))
            row["chunks"] = k
            if comp is not None:
                # One jitted program: overlap happens inside the
                # executable, invisible to the host gauges — the row's
                # claim is dispatch deletion, not an overlap window.
                row["compiled"] = True
            else:
                # Analytic overlap window: with k chunks dispatched
                # interleaved, (k-1)/k of the communication can hide
                # under other chunks' compute on an async-collective
                # backend.
                row["overlap_window"] = round((k - 1) / k, 3)
                row["overlap_fraction"] = round(SE._m_overlap.value, 6)
            if hier:
                # Per-tier analytic wire accounting: the transferable
                # number on a two-tier fabric is the cross (DCN) hop
                # carrying 1/n_local of the payload at its own wire
                # mode — the CPU rig's shared-memory "DCN" cannot show
                # it in wall-clock (docs/performance.md).
                from horovod_tpu.obs import perfmodel as PM
                n_local = hier[0]
                cost = PM.expected_hierarchical(
                    numel * itemsize, n_local, n // n_local,
                    itemsize=itemsize, mode=resolved or "fp32",
                    cross_mode=cross_mode, chunks=k,
                    block=cfg.quant_block_size)
                row["cross_precision"] = cross_mode
                row["local_wire_bytes"] = int(
                    cost.tiers["local"].wire_bytes)
                row["cross_wire_bytes"] = int(
                    cost.tiers["cross"].wire_bytes)
                flat_wire = R.ring_wire_bytes(
                    "fp32", numel * itemsize, n, cfg.quant_block_size,
                    itemsize)
                row["cross_wire_reduction"] = round(
                    flat_wire / cost.tiers["cross"].wire_bytes, 2) \
                    if cost.tiers["cross"].wire_bytes else None
    if resolved != "fp32":
        block = cfg.quant_block_size
        wire = R.ring_wire_bytes(resolved, payload, n, block, itemsize)
        wire_fp32 = R.ring_wire_bytes("fp32", payload, n, block, itemsize)
        row["wire_bytes"] = wire
        row["wire_reduction"] = round(wire_fp32 / wire, 2) if wire else None
    if n > 1:
        row["busbw_GBs"] = algbw * (2 * (n - 1) / n) / 1e9
        # effective GB/s on the logical payload — same number the n==1
        # branch labels dispatch_GBs; kept under one key for mode sweeps.
        row["dispatch_GBs"] = algbw / 1e9
    else:
        # One rank has no wire: this is dispatch + HBM throughput, and it
        # must not wear a bus-bandwidth label (round-3 verdict finding).
        row["dispatch_GBs"] = algbw / 1e9
    _attach_model(row, "allreduce", payload, n, dt, mode=resolved,
                  chunks=row.get("chunks", 1),
                  block=cfg.quant_block_size, itemsize=itemsize)
    return row


def _attach_model(row: dict, verb: str, payload: int, n: int, dt: float,
                  *, mode: str = "fp32", chunks: int = 1,
                  block: int = 512, itemsize: int = 4) -> None:
    """Feed the fenced wall-clock into the expected-vs-achieved perf
    model (obs/perfmodel) and carry its attribution on the row, so a
    sweep's JSON lines double as model-efficiency evidence."""
    if n <= 1:
        return
    from horovod_tpu.obs import perfmodel as PM
    mrow = PM.MODEL.observe(verb, payload, n, dt, mode=mode,
                            chunks=chunks, block=block, itemsize=itemsize)
    if mrow:
        row["model_efficiency"] = round(mrow["efficiency"], 4)
        row["model_expected_busbw_GBs"] = round(
            mrow["expected_busbw_gbs"], 4)
        row["model_basis"] = mrow["basis"]


def alltoall_busbw(nbytes: int, *, iters: int = 20, warmup: int = 3,
                   dtype="float32") -> dict:
    """One uniform-alltoall bandwidth point on the current global mesh.

    The MoE dispatch/combine verb (parallel/moe.py routes tokens through
    exactly this path).  Each rank scatters ``1/N`` of its payload to
    every peer, so the per-device wire traffic is ``(N-1)/N * bytes`` —
    the allgather accounting, not the allreduce one.
    """
    import horovod_tpu as hvd

    n = hvd.size()
    itemsize = np.dtype(dtype).itemsize
    # Rows must split evenly across ranks; round the element count up to
    # a multiple of n so every size lands on the uniform fast path.
    numel = max(n, -(-(nbytes // itemsize) // n) * n)
    x = hvd.per_rank_from_fn(
        lambda r: np.full((numel,), float(r + 1), dtype))

    def one():
        return hvd.alltoall(x)

    out = one()
    _fence(out)
    for _ in range(warmup):
        out = one()
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = one()
    _fence(out)
    dt = (time.perf_counter() - t0) / iters
    payload = numel * itemsize
    algbw = payload / dt
    row = {"op": "alltoall", "bytes": payload, "time_us": dt * 1e6,
           "algbw_GBs": algbw / 1e9, "ranks": n}
    if n > 1:
        row["busbw_GBs"] = algbw * ((n - 1) / n) / 1e9
        row["dispatch_GBs"] = algbw / 1e9
    else:
        # One rank's alltoall is an identity copy — dispatch only.
        row["dispatch_GBs"] = algbw / 1e9
    _attach_model(row, "alltoall", payload, n, dt, itemsize=itemsize)
    return row


def sweep(sizes=None, modes=("fp32",), schedules=("monolithic",),
          verb="allreduce", **kw) -> list[dict]:
    if sizes is None:
        sizes = [1 << p for p in range(12, 27, 2)]   # 4 KB .. 64 MB
    if verb == "alltoall":
        # Wire modes / schedules are allreduce machinery (quantized
        # reductions, rs_ag decomposition) — the alltoall sweep is plain
        # sizes x ranks.
        return [alltoall_busbw(s, **kw) for s in sizes]
    return [allreduce_busbw(s, wire_precision=m, schedule=sc, **kw)
            for sc in schedules for m in modes for s in sizes]


def hierarchy_sweep(sizes=None, cross_modes=("fp32", "int8", "fp8"),
                    n_local: int = 0, **kw) -> list[dict]:
    """Flat vs tiered-kernel vs chunked+tiered rows, cross modes swept.

    Three variants per size (see module docstring for the rig caveat):

    - ``flat``       — monolithic single-ring baseline;
    - ``tier:<nl>``  — the unchunked hierarchical kernel
      (``cfg.hierarchical_allreduce`` routing, ops/hierarchical.py);
    - ``hier:<nl>:2``— the sched executor's chunked+tiered pipeline,
      once per cross wire mode (``cfg.hierarchical_cross_precision``).
    """
    import os
    import horovod_tpu as hvd

    cfg = hvd.global_state().config
    n = hvd.size()
    nl = (n_local
          or int(os.environ.get("HVDTPU_HIERARCHICAL_LOCAL_SIZE", "0") or 0)
          or cfg.hierarchical_local_size
          or (n // 2 if n >= 4 and n % 2 == 0 else 0))
    if not (1 < nl < n) or n % nl:
        raise SystemExit(
            f"--hierarchy needs a valid two-tier split of np={n} "
            f"(got n_local={nl}); run with --cpu-devices 4 for a 2x2 rig")
    if sizes is None:
        sizes = [1 << p for p in range(16, 25, 2)]   # 64 KB .. 16 MB
    rows: list[dict] = []
    saved = (cfg.hierarchical_allreduce, cfg.hierarchical_local_size,
             cfg.hierarchical_cross_precision)
    import sys
    kw.setdefault("fence_each", True)
    # Serialize the executor's sub-program pipeline too: on a few-core
    # host the in-process XLA:CPU rendezvous intermittently deadlocks
    # when independent tiered sub-programs are in flight together (see
    # executor._FENCE_DISPATCH).  Overlap gauges read 0 under the fence,
    # which this rig could not measure honestly anyway.
    from horovod_tpu.ops.sched import executor as SE
    if os.environ.get("HVDTPU_SCHED_FENCE_DISPATCH", "") != "0":
        SE._FENCE_DISPATCH = True
    try:
        cfg.hierarchical_local_size = nl
        for s in sizes:
            print(f"# hierarchy sweep: {s} bytes", file=sys.stderr,
                  flush=True)
            cfg.hierarchical_allreduce = False
            cfg.hierarchical_cross_precision = ""
            r = allreduce_busbw(s, **kw)
            r["hierarchy"] = "flat"
            rows.append(r)
            print("#   flat ok", file=sys.stderr, flush=True)
            # Tiered monolithic kernel: flag-routed, no chunking.  It
            # bypasses the sched executor, so attach the per-tier
            # analytics here (same accounting the hier:* rows get).
            cfg.hierarchical_allreduce = True
            r = allreduce_busbw(s, **kw)
            r["hierarchy"] = f"tier:{nl}"
            from horovod_tpu.ops import reduction as R
            from horovod_tpu.obs import perfmodel as PM
            cost = PM.expected_hierarchical(
                r["bytes"], nl, n // nl, mode=r["wire_precision"] or "fp32")
            r["local_wire_bytes"] = int(cost.tiers["local"].wire_bytes)
            r["cross_wire_bytes"] = int(cost.tiers["cross"].wire_bytes)
            flat_wire = R.ring_wire_bytes("fp32", r["bytes"], n,
                                          cfg.quant_block_size, 4)
            r["cross_wire_reduction"] = round(
                flat_wire / cost.tiers["cross"].wire_bytes, 2)
            rows.append(r)
            print("#   tier-kernel ok", file=sys.stderr, flush=True)
            # Chunked+tiered schedule, every wire mode on the cross hop.
            cfg.hierarchical_allreduce = False
            for cm in cross_modes:
                cfg.hierarchical_cross_precision = (
                    "" if cm in ("", "fp32") else cm)
                r = allreduce_busbw(s, schedule=f"hier:{nl}:2", **kw)
                r["hierarchy"] = f"hier:{nl}:2"
                r.setdefault("cross_precision", cm if cm != "fp32" else "")
                rows.append(r)
                print(f"#   hier cross={cm} ok", file=sys.stderr,
                      flush=True)
    finally:
        (cfg.hierarchical_allreduce, cfg.hierarchical_local_size,
         cfg.hierarchical_cross_precision) = saved
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-devices", type=int, default=0, metavar="N",
                    help="force an N-device virtual CPU rig (multi-rank "
                    "busbw with real XLA collectives + protocol overhead; "
                    "numbers are CPU-memory-bound, not ICI)")
    ap.add_argument("--wire-precision", default="fp32", metavar="MODES",
                    help="comma-separated wire modes to sweep "
                    "(fp32,bf16,fp16,int8,fp8); each mode reports "
                    "dispatch_GBs (measured) and wire_reduction (analytic "
                    "interconnect saving vs fp32)")
    ap.add_argument("--schedule", default="monolithic", metavar="SCHEDS",
                    help="comma-separated schedules to sweep (monolithic,"
                    "rs_ag:2,compiled:rs_ag:2,...); decomposed rows "
                    "report dispatch_GBs (measured), overlap_window "
                    "(analytic (k-1)/k) and overlap_fraction (executor "
                    "gauge); compiled rows report dispatch_GBs only (one "
                    "program, host-invisible overlap)")
    ap.add_argument("--sched-mode", default=None, metavar="MODES",
                    help="alias for --schedule accepting bare sched "
                    "modes (monolithic,decomposed,compiled) alongside "
                    "descriptors; bare modes resolve through the "
                    "engine's resolver at the configured chunk count")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the schedule-sweep summary as a JSON "
                    "record (BENCH_rXX.json shape)")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes only (4KB..1MB) — the CI "
                    "perf-regress sweep; rows stay comparable with the "
                    "committed trajectory because the sentinel keys "
                    "series per size, never on a range-dependent peak")
    ap.add_argument("--verb", default="allreduce",
                    choices=("allreduce", "alltoall"),
                    help="collective to sweep; alltoall is the MoE "
                    "dispatch/combine verb and ignores wire-precision/"
                    "schedule (those are reduction machinery)")
    ap.add_argument("--hierarchy", action="store_true",
                    help="two-tier sweep: flat vs tiered kernel vs "
                    "chunked+tiered (hier:<n_local>:2) with fp32/int8/fp8 "
                    "on the cross hop; hier rows carry analytic per-tier "
                    "wire bytes (the CPU rig cannot show the 1/n_local "
                    "win in wall-clock — see module docstring)")
    args = ap.parse_args()
    if args.cpu_devices:
        from horovod_tpu.utils.cpurig import force_cpu_platform
        force_cpu_platform(args.cpu_devices)
    import horovod_tpu as hvd
    hvd.init()
    # Benchmarks opt out of the size floor: the point is to measure every
    # mode at every size, not to second-guess the resolver.
    hvd.global_state().config.quant_min_bytes = 0
    modes = [m.strip() for m in args.wire_precision.split(",") if m.strip()]
    sched_src = args.sched_mode or args.schedule
    schedules = [s.strip() for s in sched_src.split(",") if s.strip()]
    sizes = [1 << p for p in range(12, 21, 2)] if args.quick else None
    if args.hierarchy:
        hsizes = sizes if args.quick else None
        rows = hierarchy_sweep(sizes=hsizes)
        for r in rows:
            print(json.dumps(r))
        # Per-variant summary at >= 1 MB: measured wall-clock ratio vs
        # flat (expected <= 1 on the shared-memory rig) and the analytic
        # cross_wire_reduction (the number that transfers to a real
        # two-tier fabric).
        base = {r["bytes"]: r for r in rows if r["hierarchy"] == "flat"}
        summary = []
        groups: dict = {}
        for r in rows:
            if r["hierarchy"] == "flat":
                continue
            groups.setdefault(
                (r["hierarchy"], r.get("cross_precision", "")),
                []).append(r)
        for (hv, cm), grp in sorted(groups.items()):
            big = [r for r in grp
                   if r["bytes"] >= (1 << 20) and r["bytes"] in base]
            if not big:
                continue
            ratios = [r["dispatch_GBs"] / base[r["bytes"]]["dispatch_GBs"]
                      for r in big]
            rec = {
                "metric": f"allreduce_{hv}_vs_flat_at_1MB_plus",
                "cross_precision": cm,
                "measured_dispatch_ratio": round(float(np.mean(ratios)), 3),
                "cross_wire_reduction": big[-1].get("cross_wire_reduction"),
                "local_wire_bytes": big[-1].get("local_wire_bytes"),
                "cross_wire_bytes": big[-1].get("cross_wire_bytes"),
                "ranks": big[-1]["ranks"],
            }
            summary.append(rec)
            print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump({"schedule_sweep": summary, "rows": rows}, fh,
                          indent=1)
        return
    rows = sweep(sizes=sizes, modes=modes, schedules=schedules,
                 verb=args.verb)
    for r in rows:
        print(json.dumps(r))
    key = "busbw_GBs" if "busbw_GBs" in rows[0] else "dispatch_GBs"
    if args.verb == "alltoall":
        best = max(rows, key=lambda r: r[key])
        metric = ("alltoall_busbw_peak" if key == "busbw_GBs"
                  else "alltoall_dispatch_peak")
        print(json.dumps({"metric": metric, "value": round(best[key], 2),
                          "unit": "GB/s", "at_bytes": best["bytes"],
                          "ranks": best["ranks"]}))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump({"schedule_sweep": [], "rows": rows}, fh,
                          indent=1)
        return
    by_mode = {m: [r for r in rows if r["wire_precision"] == m]
               for m in modes}
    base_rows = by_mode.get("fp32") or rows
    best = max(base_rows, key=lambda r: r[key])
    metric = ("allreduce_busbw_peak" if key == "busbw_GBs"
              else "allreduce_dispatch_peak")
    print(json.dumps({"metric": metric, "value": round(best[key], 2),
                      "unit": "GB/s", "at_bytes": best["bytes"],
                      "ranks": best["ranks"]}))
    if len(modes) > 1 and "fp32" in by_mode:
        # Mode comparison at >= 4 MB: measured wall-clock ratio AND the
        # analytic wire saving, per mode.
        base = {r["bytes"]: r for r in by_mode["fp32"]}
        for m in modes:
            if m == "fp32":
                continue
            big = [r for r in by_mode[m]
                   if r["bytes"] >= (1 << 22) and r["bytes"] in base]
            if not big:
                continue
            ratios = [r["dispatch_GBs"] / base[r["bytes"]]["dispatch_GBs"]
                      for r in big]
            print(json.dumps({
                "metric": f"allreduce_{m}_vs_fp32_at_4MB_plus",
                "measured_dispatch_ratio": round(float(np.mean(ratios)), 3),
                "wire_reduction": big[0].get("wire_reduction"),
                "ranks": big[0]["ranks"],
            }))
    summary = []
    if len(schedules) > 1 and "monolithic" in schedules:
        # Schedule comparison at >= 4 MB: measured wall-clock ratio of
        # each decomposed variant vs monolithic AT THE SAME WIRE MODE
        # (mixing modes would divide e.g. fp32 decomposed by int8
        # monolithic), with the analytic overlap window and the
        # executor's measured in-flight fraction.
        by_sched: dict = {}
        base: dict = {}
        for r in rows:
            mkey = (r["wire_precision"], r["bytes"])
            sc = r.get("schedule", "monolithic")
            if sc == "monolithic":
                base[mkey] = r
            else:
                by_sched.setdefault(sc, []).append(r)
        for sc, sc_rows in sorted(by_sched.items()):
            big = [r for r in sc_rows
                   if r["bytes"] >= (1 << 22)
                   and (r["wire_precision"], r["bytes"]) in base]
            if not big:
                continue
            ratios = [
                r["dispatch_GBs"]
                / base[(r["wire_precision"], r["bytes"])]["dispatch_GBs"]
                for r in big]
            rec = {
                "metric": f"allreduce_{sc}_vs_monolithic_at_4MB_plus",
                "measured_dispatch_ratio": round(float(np.mean(ratios)), 3),
                "overlap_window": big[0].get("overlap_window"),
                "overlap_fraction": big[0].get("overlap_fraction"),
                "ranks": big[0]["ranks"],
            }
            summary.append(rec)
            print(json.dumps(rec))
    if len(schedules) > 1:
        # Compiled vs dispatched at the SAME wire mode, chunk count and
        # size.  The compiled backend's claim is dispatch DELETION, so
        # the honest comparison window is the dispatch-bound sizes
        # (<= 64KB: there the per-unit host dispatch dominates wall
        # clock on every backend, CPU rig included — unlike the
        # overlap-window numbers above, this ratio transfers).
        from horovod_tpu.ops import sched as S
        disp: dict = {}
        comp_rows = []
        for r in rows:
            sc = r.get("schedule") or ""
            ck = S.parse_compiled_descriptor(sc)
            if ck is not None:
                comp_rows.append((ck, r))
            else:
                kd = S.parse_descriptor(sc)
                if kd is not None:
                    disp[(r["wire_precision"], r["bytes"], kd)] = r
        by_key: dict = {}
        for ck, r in comp_rows:
            mate = disp.get((r["wire_precision"], r["bytes"], ck))
            if mate and r["bytes"] <= (1 << 16):
                by_key.setdefault((r["wire_precision"], ck), []).append(
                    (r["dispatch_GBs"] / mate["dispatch_GBs"], r))
        for (wp, ck), pairs in sorted(by_key.items()):
            ratios = [p[0] for p in pairs]
            rec = {
                "metric": (f"allreduce_{wp}_compiled_vs_rs_ag:{ck}"
                           "_at_64KB_minus"),
                "measured_dispatch_ratio": round(float(np.mean(ratios)), 3),
                "sizes": [p[1]["bytes"] for p in pairs],
                "ranks": pairs[0][1]["ranks"],
            }
            summary.append(rec)
            print(json.dumps(rec))
    if args.out:
        # Always honored — a sweep without a monolithic baseline still
        # writes its rows (summary is empty then, not silently dropped).
        with open(args.out, "w") as fh:
            json.dump({"schedule_sweep": summary, "rows": rows}, fh,
                      indent=1)


if __name__ == "__main__":
    main()
