r"""Which part of its program every device op belongs to.

The program names the parts of what it compiles (``horovod_tpu.obs.trace
.region``: ``hvd.block.mixer``, ``hvd.block.mlp``, ``hvd.moe.experts``,
``hvd.optim``, ...): every instruction traced inside a region carries the
name in its ``metadata.op_name``, the innermost last, through ``scan``,
``checkpoint`` and autodiff, which write ``transpose(`` and
``rematted_computation`` into the same path.  A device op of the trace is
named after its instruction (``%fusion.491 = ...``), and the xplane file
holds the map from one to the other itself: its ``/host:metadata`` plane
has one entry a program that ran, named ``<module>(<program id>)`` like the
program's runs on the device, with the compiled module in a bytes stat
``Hlo Proto``.  So nothing is handed over: this file reads the map out of
the trace that ``trace.reduce_run`` picked, gives every op of ``red["ops"]``
the program run that holds it and from there its region and its phase
(``forward``, ``recompute``, ``backward``), and sums the seconds.  What
the compiler makes itself carries no region of its own (a scan's slice of
the stacked weights, a copy, a product it split in two): it is counted
with the work it is part of (``_module_table`` has the rule).

``jax.profiler.ProfileData`` shows no event metadata and no ``xplane_pb2``
is installed, so the few fields needed are described here, by number, for
``google.protobuf`` to parse.  A program without regions (an older commit)
yields a table with no region in it, and every reducer built on this
returns None.

    python -m chipbench.op_regions <file.xplane.pb> [<regex>]

prints the table of any trace of this program, a CPU trace too; with a
regular expression, every op whose short name it finds, with the op's
program, regions and phase (``'fusion\.491 '``: what is this fusion?).
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
import sys
import time

from . import trace

PHASES = ("forward", "recompute", "backward")
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
_REGION = re.compile(r"hvd\.[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)*")
_CONTAINER_OPCODES = ("while", "conditional", "call")
# what hands values on without being work on them: no region passes through
_PASS_THROUGH = _CONTAINER_OPCODES + ("tuple", "get-tuple-element",
                                      "parameter")
_HEAVY_OPCODES = ("dot", "convolution", "custom-call")
HEAVIEST = 5


# -- the few messages of xplane.proto and hlo.proto that are read -----------

@functools.lru_cache(maxsize=None)
def _messages() -> dict:
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_op_regions.proto", package="chipbench",
        syntax="proto3")
    kinds = {"str": F.TYPE_STRING, "int": F.TYPE_INT64,
             "bytes": F.TYPE_BYTES}
    for name, fields in {
        "XStat": [("metadata_id", 1, "int"), ("bytes_value", 6, "bytes")],
        "XEventMetadata": [("name", 2, "str"), ("stats", 5, "*XStat")],
        "XStatMetadata": [("name", 2, "str")],
        "EventEntry": [("key", 1, "int"), ("value", 2, "XEventMetadata")],
        "StatEntry": [("key", 1, "int"), ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, "str"), ("event_metadata", 4, "*EventEntry"),
                   ("stat_metadata", 5, "*StatEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
        "OpMetadata": [("op_name", 2, "str")],
        "HloInstructionProto": [
            ("name", 1, "str"), ("opcode", 2, "str"),
            ("metadata", 7, "OpMetadata"), ("id", 35, "int"),
            ("operand_ids", 36, "*int"),
            ("called_computation_ids", 38, "*int")],
        "HloComputationProto": [
            ("name", 1, "str"), ("instructions", 2, "*HloInstructionProto"),
            ("id", 5, "int")],
        "HloModuleProto": [("name", 1, "str"),
                           ("computations", 3, "*HloComputationProto")],
        "HloProto": [("hlo_module", 1, "HloModuleProto")],
    }.items():
        m = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            many, kind = kind.startswith("*"), kind.lstrip("*")
            f = m.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if many else F.LABEL_OPTIONAL,
                type=kinds.get(kind, F.TYPE_MESSAGE))
            if kind not in kinds:
                f.type_name = ".chipbench." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {n: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench." + n))
        for n in ("XSpace", "HloProto")}


def region_of(op_name: str):
    """``(regions, phase)`` of an instruction's ``op_name``: every
    ``hvd.<...>`` of the path in order (the last is the innermost; one
    may stand inside ``jvp(...)`` parentheses), and which pass of the step
    the instruction belongs to."""
    phase = "recompute" if "rematted_computation" in op_name else \
        "backward" if "transpose(" in op_name else "forward"
    return tuple(_REGION.findall(op_name)), phase


def _vote(found):
    """The ``(regions, phase)`` most of ``found`` have, or None."""
    count: dict = {}
    for regions, phase in found:
        count[regions, phase] = count.get((regions, phase), 0) + 1
    return max(count, key=count.get) if count else None


def _module_table(module) -> dict:
    """``{instruction name: (regions, phase, opcode)}`` of one compiled
    module.  An instruction whose own ``op_name`` carries no region (the
    compiler's own: a scan's slice of the stacked weights, a copy, a
    product it split) is given one, by the first of these that finds
    any: a fusion takes the region of the products and kernels in its
    fused computation; an instruction takes the region of the
    instructions that use its result (whose work it is part of: the
    slice of ``wq`` belongs to the mixer that multiplies by it), a
    producer's producer in turn; a fusion takes the region most of its
    fused instructions carry.  What only feeds a loop's carry, a tuple
    or the program's result stays without."""
    comps = {c.id: c for c in module.computations}
    found, opcode, fused, users, own_phase = {}, {}, {}, {}, {}
    for c in module.computations:
        names = {i.id: i.name for i in c.instructions}
        for i in c.instructions:
            opcode[i.name] = i.opcode
            regions, own_phase[i.name] = region_of(i.metadata.op_name)
            if regions:
                found[i.name] = (regions, own_phase[i.name])
            fused[i.name] = [j.name for cid in i.called_computation_ids
                             if i.opcode == "fusion" and cid in comps
                             for j in comps[cid].instructions]
            if i.opcode not in _PASS_THROUGH:
                for operand in i.operand_ids:
                    users.setdefault(names.get(operand), []).append(i.name)
    bare = [n for n in opcode if n not in found
            and opcode[n] not in _PASS_THROUGH]
    for n in bare:                                  # its products
        got = _vote(found[j] for j in fused[n]
                    if j in found and opcode[j] in _HEAVY_OPCODES)
        if got:
            found[n] = got
    fresh = True
    while fresh:                                    # its users, in turn
        fresh = {n: _vote(found[u] for u in users.get(n, ()) if u in found)
                 for n in bare if n not in found}
        fresh = {n: got for n, got in fresh.items() if got}
        found.update(fresh)
    for n in bare:                                  # its other instructions
        if n not in found:
            got = _vote(found[j] for j in fused[n] if j in found)
            if got:
                found[n] = got
    return {n: found.get(n, ((), own_phase[n])) + (opcode[n],)
            for n in opcode}


def read_programs(path: str) -> dict:
    """``{"<module>(<program id>)": {instruction name: (regions, phase,
    opcode)}}`` of every program the xplane file holds the compiled
    module of, and under ``"bytes"`` the size of each ``Hlo Proto``."""
    msg = _messages()
    space = msg["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    programs, sizes = {}, {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) != "Hlo Proto":
                    continue
                hlo = msg["HloProto"]()
                hlo.ParseFromString(stat.bytes_value)
                programs[entry.value.name] = _module_table(hlo.hlo_module)
                sizes[entry.value.name] = len(stat.bytes_value)
    return {"programs": programs, "bytes": sizes}


def host_events(path: str) -> dict:
    """``ops`` and ``modules`` as ``trace.read_xplane`` gives them, from a
    trace taken with no TPU: XLA's CPU thunks are host events that carry
    ``hlo_op``, ``hlo_module``, ``program_id`` and ``run_id``; a program
    run is what lies between its first op and its last."""
    import jax
    ops, runs = [], {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "hlo_op" not in st or "hlo_module" not in st:
                    continue
                t, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                ops.append((0, st["hlo_op"], t, d))
                key = (f"{st['hlo_module']}({st.get('program_id', 0)})",
                       st.get("run_id", 0))
                lo, hi = runs.get(key, (t, t + d))
                runs[key] = (min(lo, t), max(hi, t + d))
    return {"ops": sorted(ops, key=lambda o: o[2]),
            "modules": sorted((0, name, lo, hi - lo)
                              for (name, _), (lo, hi) in runs.items())}


# -- every op of a trace in its program, region and phase --------------------

def place(ops, modules, programs: dict, lo=float("-inf"), hi=float("inf")):
    """``(program, regions, phase, op name, seconds)`` of every op in
    ``ops`` ((device, name, start, dur), as ``red["ops"]``) that starts
    in ``[lo, hi]`` and only holds no other ops.  An op's program is the
    run of ``modules`` on its device that holds its start; its
    instruction the left side of its name.  ``program`` is the run's name
    without the program id, ``(no program)`` for an op outside every run;
    such an op still gets the region of its instruction's name where
    every module that has the name agrees.  A run under an id that
    ``programs`` lacks is the one program of its module's name, if there
    is one: XLA's CPU thunks keep the id they were compiled under, and a
    program read back from the compile cache gets a new one."""
    runs: dict = {}
    of_module: dict = {}       # module name -> its programs
    for key in programs:
        of_module.setdefault(key.split("(")[0], []).append(key)
    for dv, name, t, d in modules:
        same = [name] if name in programs else \
            of_module.get(name.split("(")[0], ())
        runs.setdefault(dv, []).append(
            (t, t + d, name, programs[same[0]] if len(same) == 1 else None))
    runs = {dv: sorted(rs, key=lambda r: r[:3]) for dv, rs in runs.items()}
    starts = {dv: [r[0] for r in rs] for dv, rs in runs.items()}
    anywhere: dict = {}
    for table in programs.values():
        for instr, entry in table.items():
            anywhere.setdefault(instr, set()).add(entry)
    instr_of: dict = {}        # op name -> its instruction, None: a container
    for dv, name, t, d in ops:
        if not lo <= t <= hi:
            continue
        if name not in instr_of:
            instr_of[name] = None if trace._CONTAINER.match(name) else \
                name.partition(" = ")[0].lstrip("%")
        instr = instr_of[name]
        if instr is None:
            continue
        k = bisect.bisect_right(starts.get(dv, ()), t) - 1
        run = runs[dv][k] if k >= 0 and t <= runs[dv][k][1] else None
        if run and run[3] is not None:
            found = run[3].get(instr)
        else:
            found = anywhere.get(instr, ())
            found = next(iter(found)) if len(found) == 1 else None
        regions, phase, opcode = found or ((), "forward", "")
        if opcode in _CONTAINER_OPCODES:
            continue
        yield (run[2].split("(")[0] if run else "(no program)",
               regions, phase, name, d)


def table(rows, n_dev: int = 1) -> dict:
    """Seconds (mean over devices) by program, innermost region and
    phase, the five heaviest ops under each and of each program."""
    out: dict = {}
    shorts: dict = {}
    for program, regions, phase, name, d in rows:
        d = d / n_dev
        p = out.setdefault(program, {"seconds": 0.0, "regions": {},
                                     "ops": {}})
        p["seconds"] += d
        r = p["regions"].setdefault(regions[-1] if regions else "", {})
        ph = r.setdefault(phase, {"seconds": 0.0, "ops": {}})
        ph["seconds"] += d
        if name not in shorts:
            shorts[name] = trace.short_name(name)
        short = shorts[name]
        ph["ops"][short] = ph["ops"].get(short, 0.0) + d
        where = f"{regions[-1] if regions else '(no region)'} {phase}"
        p["ops"][short, where] = p["ops"].get((short, where), 0.0) + d
    top = lambda ops: sorted(ops.items(), key=lambda kv: -kv[1])[:HEAVIEST]
    for p in out.values():
        p["ops"] = [[n, where, s] for (n, where), s in top(p["ops"])]
        for r in p["regions"].values():
            for ph in r.values():
                ph["ops"] = [[n, s] for n, s in top(ph["ops"])]
    return out


def lines(tab: dict):
    """One line a program: its regions' shares, each split by phase where
    there is more than the forward one, and its heaviest ops with the
    region and phase of each."""
    for program, p in sorted(tab.items(), key=lambda kv: -kv[1]["seconds"]):
        total = p["seconds"] or 1.0
        parts = []
        for region, phases in sorted(
                p["regions"].items(),
                key=lambda kv: -sum(x["seconds"] for x in kv[1].values())):
            s = sum(x["seconds"] for x in phases.values())
            split = "" if set(phases) == {"forward"} else " (" + ", ".join(
                f"{ph} {100 * phases[ph]['seconds'] / total:.1f}"
                for ph in PHASES if ph in phases) + ")"
            parts.append(f"{region or '(no region)'} "
                         f"{100 * s / total:.1f}%{split}")
        yield (f"{program} {p['seconds']:.4f} s: " + "; ".join(parts)
               + " | heaviest: " + "; ".join(
                   f"{n} [{where}] {s:.4f}" for n, where, s in p["ops"]))


def placed(red: dict, cell: dict):
    """The rows of ``place`` for this cell's traced run, aggregated to
    ``[(program, regions, phase, seconds)]`` (mean over devices), or None
    where no op of the trace has a region.  Parsed once a run and kept in
    ``red``; the first call writes ``out/<cell>/op_regions.json`` and logs
    the table, one line a program."""
    if "op_regions" in red:
        return red["op_regions"]
    t0 = time.perf_counter()
    name = cell["cell"]["name"]
    paths = glob.glob(os.path.join(OUT, name, "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    read = read_programs(max(paths, key=os.path.getmtime)) if paths \
        else {"programs": {}, "bytes": {}}
    n_dev = len(red["devices"])
    rows = list(place(red["ops"], red["modules"], read["programs"],
                      red["lo"], red["hi"]))
    agg: dict = {}
    for program, regions, phase, _, d in rows:
        key = (program, regions, phase)
        agg[key] = agg.get(key, 0.0) + d / n_dev
    red["op_regions"] = None
    if any(regions for _, regions, _ in agg):
        red["op_regions"] = [k + (s,) for k, s in agg.items()]
        tab = table(rows, n_dev)
        took = time.perf_counter() - t0
        with open(os.path.join(OUT, name, "op_regions.json"), "w") as f:
            json.dump({"programs": tab, "hlo_proto_bytes": read["bytes"],
                       "reader_s": took}, f, indent=1)
        for line in lines(tab):
            print("[chipbench] regions:", line, file=sys.stderr, flush=True)
        print(f"[chipbench] regions: {len(rows)} ops placed against "
              f"{len(read['programs'])} modules "
              f"({sum(read['bytes'].values())} B of Hlo Proto) in "
              f"{took:.2f} s", file=sys.stderr, flush=True)
    return red["op_regions"]


def share(rows, regions, phases=None, programs=None):
    """Percent of the seconds of ``rows`` (as ``placed`` returns them)
    inside programs whose name holds one of ``programs`` (all, if None)
    that lie in an op with one of ``regions`` anywhere in its path (a
    name matches itself and what is dotted under it; ``[]`` means the
    ops with no region) and, if given, one of ``phases``.  None where
    those programs ran nothing."""
    under = lambda r, p: r == p or r.startswith(p + ".")
    total = part = 0.0
    for program, path, phase, s in rows:
        if programs and not any(p in program for p in programs):
            continue
        total += s
        hit = any(under(r, p) for r in path for p in regions) if regions \
            else not path
        if hit and (not phases or phase in phases):
            part += s
    return 100.0 * part / total if total else None


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) not in (1, 2):
        print(__doc__[__doc__.index("    python -m"):], file=sys.stderr)
        return 2
    ev = trace.read_xplane(argv[0])
    if not ev["ops"]:
        ev = host_events(argv[0])
    n_dev = len({o[0] for o in ev["ops"]}) or 1
    rows = list(place(ev["ops"], ev["modules"],
                      read_programs(argv[0])["programs"]))
    if len(argv) == 2:          # where is fusion.491?
        found: dict = {}
        for program, regions, phase, name, d in rows:
            short = trace.short_name(name)
            if re.search(argv[1], short):
                c = found.setdefault((program, short, regions, phase), [0, 0])
                c[0] += 1
                c[1] += d / n_dev
        for (program, short, regions, phase), (n, s) in sorted(
                found.items(), key=lambda kv: -kv[1][1]):
            print(f"{s:.6f} s x{n} {program} {short} "
                  f"[{'/'.join(regions) or '(no region)'} {phase}]")
        return 0
    tab = table(rows, n_dev)
    for line in lines(tab):
        print(line)
    for program, p in tab.items():
        for region, phases in p["regions"].items():
            for phase, x in phases.items():
                for n, s in x["ops"]:
                    print(f"  {program} {region or '(no region)'} {phase} "
                          f"{s:.6f} {n}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
