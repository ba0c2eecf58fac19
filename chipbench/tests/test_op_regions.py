"""Tests of the reader of the program's regions; seconds on the CPU.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_op_regions.py -q

``op_regions`` on an xplane file the test makes itself (a jitted scan under
``checkpoint`` and ``grad`` with regions, on the CPU), and on
``testdata/hand_regions.json`` for what a CPU trace does not hold: programs
that share an instruction name, a fusion without metadata, ops in no
program run, a trace without a region.  The eleven ``*_share`` specs under
``layer_metrics/`` are read off the hand-built trace with the value worked
out beside each; ``BENCHMARK.json`` does not list them yet.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import re
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)

from chipbench import op_regions, run as runmod, trace  # noqa: E402

_BENCH = os.path.join(_ROOT, "chipbench")
#: metric -> its value on the hand-built trace, in percent.  The trace's
#: 0.78 s: mixer 0.05 + 0.04 + 0.05 + 0.09 = 0.23; mlp (with the moe
#: regions in it, and copy.14, which feeds it) 0.06 + 0.04 + 0.06 + 0.06 +
#: 0.06 + 0.045 + 0.015 = 0.34; head and loss 0.03 + 0.04 + 0.03 = 0.10;
#: optim 0.06 + 0.02 = 0.08; no region 0.02 + 0.01 = 0.03; recomputed
#: 0.06.  The prefill programs' 0.30 s: mixer 0.09, mlp 0.16; the decode
#: program's 0.15 s: 0.05, 0.06.
_SHARES = {
    "step_unnamed_share": 100 * 0.03 / 0.78,
    "step_recompute_share": 100 * 0.06 / 0.78,
    "step_optim_share": 100 * 0.08 / 0.78,
    "step_mixer_share": 100 * 0.23 / 0.78,
    "step_mlp_share": 100 * 0.34 / 0.78,
    "step_head_loss_share": 100 * 0.10 / 0.78,
    "serve_unnamed_share": 100 * 0.03 / 0.78,
    "prefill_mixer_share": 30.0,
    "prefill_mlp_share": 100 * 0.16 / 0.30,
    "decode_mixer_share": 100 * 0.05 / 0.15,
    "decode_mlp_share": 40.0,
}


def _hand():
    return json.load(open(os.path.join(_BENCH, "testdata",
                                       "hand_regions.json")))


def _write_xplane(path, programs):
    """An xplane file with nothing but the ``/host:metadata`` plane:
    ``programs`` as the fixture lists them."""
    msg = op_regions._messages()
    space = msg["XSpace"]()
    plane = space.planes.add(name="/host:metadata")
    plane.stat_metadata.add(key=1).value.name = "Hlo Proto"
    for k, (name, computations) in enumerate(programs.items(), 1):
        hlo = msg["HloProto"]()
        hlo.hlo_module.name = name.split("(")[0]
        for c in computations:
            comp = hlo.hlo_module.computations.add(name=c["name"], id=c["id"])
            for i in c["instructions"]:
                instr = comp.instructions.add(name=i["name"], id=i["id"],
                                              opcode=i["opcode"])
                instr.metadata.op_name = i["op_name"]
                instr.operand_ids.extend(i.get("operands", []))
                instr.called_computation_ids.extend(i.get("calls", []))
        entry = plane.event_metadata.add(key=k)
        entry.value.name = name
        entry.value.stats.add(metadata_id=1,
                              bytes_value=hlo.SerializeToString())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def _hand_red(tmp_path, strip=False):
    """The hand-built trace as a reducer sees it, its programs in an
    xplane file where ``op_regions.placed`` looks for one; with ``strip``
    the programs are those of a tree from before the regions."""
    t = _hand()
    programs = t["programs"]
    if strip:
        programs = json.loads(re.sub(r"hvd\.[\w.]+/", "",
                                     json.dumps(programs))
                              .replace("jvp(hvd.loss)", "jvp()"))
        assert "hvd." not in json.dumps(programs)
    _write_xplane(str(tmp_path / "hand" / "trace" / "plugins" / "profile"
                      / "1" / "hand.xplane.pb"), programs)
    ev = {k: [tuple(e) for e in t[k]] for k in ("ops", "modules", "spans")}
    return dict(trace.reduce_events(ev), **ev)


def _value(metric, red):
    spec = runmod.load_json(os.path.join(_BENCH, "layer_metrics",
                                         metric + ".json"))
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    return reducer.reduce(red, {}, {"cell": {"name": "hand"}, "spec": spec})


@pytest.fixture
def op_regions_out(tmp_path, monkeypatch):
    """``op_regions.json`` goes under a directory of the test's own."""
    monkeypatch.setattr(op_regions, "OUT", str(tmp_path))
    return tmp_path


def test_op_regions_region_and_phase_of_a_path():
    of = op_regions.region_of
    assert of("jit(step)/jvp()/while/body/closed_call/hvd.block.mlp/"
              "bsd,df->bsf/dot_general") == (("hvd.block.mlp",), "forward")
    assert of("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
              "hvd.block.mlp/hvd.moe.experts/while/body/dot_general") == (
        ("hvd.block.mlp", "hvd.moe.experts"), "backward")
    assert of("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
              "rematted_computation/hvd.block.mixer/tanh") == (
        ("hvd.block.mixer",), "recompute")
    # a region entered under the transform stands inside its parentheses
    assert of("jit(step)/transpose(jvp(hvd.loss))/add_any") == (
        ("hvd.loss",), "backward")
    # a kernel's name is no region, nor is a span's
    assert of("jit(step)/jvp()/hvd_flash_fwd") == ((), "forward")
    assert of("") == ((), "forward")


def test_op_regions_two_programs_share_an_instruction_name(tmp_path,
                                                           op_regions_out):
    red = _hand_red(tmp_path)
    read = op_regions.read_programs(glob.glob(str(
        tmp_path / "hand" / "trace" / "plugins" / "profile" / "*" / "*"))[0])
    assert set(read["programs"]) == set(_hand()["programs"])
    assert all(b > 0 for b in read["bytes"].values())
    rows = list(op_regions.place(red["ops"], red["modules"],
                                 read["programs"], red["lo"], red["hi"]))
    by_start = {(p, trace.short_name(n).split()[0], round(d, 3)): r
                for p, r, _, n, d in rows}
    # fusion.1 is the mixer's in bucket 1, the mlp's in bucket 2 and in
    # the decode program, and in no run it is nobody's
    assert by_start["jit_hvd_serve_prefill", "fusion.1", 0.05] == \
        ("hvd.block.mixer",)
    assert by_start["jit_hvd_serve_prefill", "fusion.1", 0.06] == \
        ("hvd.block.mlp",)
    assert by_start["jit_hvd_serve_decode", "fusion.1", 0.06] == \
        ("hvd.block.mlp",)
    assert by_start["(no program)", "fusion.1", 0.01] == ()
    # a name that one module alone has keeps its region outside a run
    assert by_start["(no program)", "fusion.12", 0.02] == ("hvd.optim",)
    # what no region names is its user's, and its user's producer's; but
    # no region passes through a tuple and its element
    decode = read["programs"]["jit_hvd_serve_decode(3)"]
    assert decode["copy.15"] == (("hvd.block.mixer",), "forward", "copy")
    assert decode["copy-done.9"][0] == ("hvd.block.mixer",)
    assert read["programs"]["jit_hvd_serve_prefill(1)"]["copy.5"][0] == ()
    # the op that only holds others is left out, as trace._CONTAINER does
    assert not any("while.3" in n for _, _, _, n, _ in rows)
    assert sum(d for *_, d in rows) == pytest.approx(0.78)


def test_op_regions_run_under_an_id_the_trace_lacks(tmp_path, op_regions_out):
    """XLA's CPU thunks keep the program id they were compiled under, and
    a program read back from the compile cache is given a new one: such a
    run is the one program of its module's name; where two programs have
    the name (the prefill's buckets), it is no program's."""
    red = _hand_red(tmp_path)
    programs = op_regions.read_programs(glob.glob(str(
        tmp_path / "hand" / "trace" / "plugins" / "profile" / "*" / "*"))[0]
    )["programs"]
    place = lambda modules: sorted(
        (p, r, ph, n, round(d, 6)) for p, r, ph, n, d in op_regions.place(
            red["ops"], modules, programs, red["lo"], red["hi"]))
    stale = [(dv, re.sub(r"\(\d+\)", "(99)", n), t, d)
             for dv, n, t, d in red["modules"]]
    assert stale != red["modules"]
    one = lambda rows: [r for r in rows if r[0] != "jit_hvd_serve_prefill"]
    assert one(place(stale)) == one(place(red["modules"]))
    # fusion.1 is the mixer's in one bucket and the mlp's in the other
    assert {r[1] for r in place(stale)
            if r[0] == "jit_hvd_serve_prefill" and "fusion.1 " in r[3] + " "
            } == {()}


def test_op_regions_fusion_without_metadata_takes_its_products_region(
        tmp_path, op_regions_out):
    red = _hand_red(tmp_path)
    rows = op_regions.placed(red, {"cell": {"name": "hand"}})
    # two of its five instructions are the expert's, three the shared
    # expert's adds: the product decides
    assert ("jit_hvd_serve_prefill", ("hvd.block.mlp", "hvd.moe.experts"),
            "forward", pytest.approx(0.06)) in rows
    assert red["op_regions"] is rows                     # parsed once
    os.remove(glob.glob(str(tmp_path / "hand" / "trace" / "plugins"
                            / "profile" / "*" / "*"))[0])
    assert op_regions.placed(red, {"cell": {"name": "hand"}}) is rows


@pytest.mark.parametrize("metric", sorted(_SHARES))
def test_op_regions_share_on_the_hand_trace(metric, tmp_path,
                                            op_regions_out):
    red = _hand_red(tmp_path)
    assert _value(metric, red) == pytest.approx(_SHARES[metric])


def test_op_regions_shares_of_a_program_add_up(tmp_path, op_regions_out):
    red = _hand_red(tmp_path)
    rows = op_regions.placed(red, {"cell": {"name": "hand"}})
    for programs in (None, ["jit_step"], ["hvd_serve_prefill"]):
        parts = [op_regions.share(rows, r, None, programs) for r in (
            [], ["hvd.block.mixer"], ["hvd.block.mlp"],
            ["hvd.embed", "hvd.head", "hvd.loss"], ["hvd.optim"])]
        assert sum(parts) == pytest.approx(100.0)
    phases = [op_regions.share(rows, ["hvd"], [ph], ["jit_step"])
              for ph in op_regions.PHASES]
    # jit_step's 0.30 s: forward 0.06 + 0.045 + 0.015, recompute 0.06,
    # backward 0.09 + 0.03
    assert phases == [pytest.approx(40.0), pytest.approx(20.0),
                      pytest.approx(40.0)]
    assert op_regions.share(rows, ["hvd"], None, ["jit_nothing"]) is None


def test_op_regions_table_and_log(tmp_path, op_regions_out, capsys):
    red = _hand_red(tmp_path)
    op_regions.placed(red, {"cell": {"name": "hand"}})
    out = json.load(open(tmp_path / "hand" / "op_regions.json"))
    step = out["programs"]["jit_step"]
    assert step["seconds"] == pytest.approx(0.30)
    assert step["regions"]["hvd.block.mlp"]["recompute"]["seconds"] == \
        pytest.approx(0.06)
    assert step["regions"]["hvd.block.mlp"]["recompute"]["ops"][0][0] \
        .startswith("fusion.9 fusion bf16[4,2048,14336]")
    # the scan's slice of the weights lies where the product it feeds does
    assert "" not in step["regions"]
    assert [n.split()[0] for n, _ in
            step["regions"]["hvd.block.mlp"]["forward"]["ops"]] == [
        "fusion.13", "copy.14"]
    # the heaviest op of a program comes with its region and phase
    assert step["ops"][0][:2] == ["fusion.10 fusion bf16[4096,32,128]",
                                  "hvd.block.mixer backward"]
    pre = out["programs"]["jit_hvd_serve_prefill"]
    assert pre["seconds"] == pytest.approx(0.30)           # both buckets
    assert set(pre["regions"]) == {
        "hvd.block.mixer", "hvd.block.mlp", "hvd.moe.experts",
        "hvd.moe.route", "hvd.head", ""}
    assert set(out["hlo_proto_bytes"]) == set(_hand()["programs"])
    err = capsys.readouterr().err
    line = [l for l in err.splitlines() if "regions: jit_step" in l][0]
    assert "hvd.block.mlp 40.0% (forward 20.0, recompute 20.0)" in line
    assert "fusion.10 fusion bf16[4096,32,128] [hvd.block.mixer backward]" \
        in line
    assert "reduce.4 reduce f32[2048] [hvd.moe.route forward]" in err


def test_op_regions_none_where_the_program_has_no_region(tmp_path,
                                                         op_regions_out):
    red = _hand_red(tmp_path, strip=True)
    assert op_regions.placed(red, {"cell": {"name": "hand"}}) is None
    assert not os.path.exists(tmp_path / "hand" / "op_regions.json")
    for metric in _SHARES:
        assert _value(metric, red) is None
    # and where the trace holds no module at all
    bare = _hand_red(tmp_path / "bare")
    os.remove(glob.glob(str(tmp_path / "bare" / "hand" / "trace" / "plugins"
                            / "profile" / "*" / "*"))[0])
    assert op_regions.placed(bare, {"cell": {"name": "hand"}}) is None


def test_op_regions_specs_wait_for_their_manifest_entries():
    """The eleven specs are written; BENCHMARK.json lists none of them yet
    (PERF.md section 7: ``test_glm_cell_is_found_whole`` holds the tail of
    ``per_layer`` by position, and a PR that is no ``benchmark`` one may
    only append).  An entry that a later PR adds has to agree with its
    spec, and none may list a cell whose test holds its metric set."""
    manifest = runmod.load_json(os.path.join(_ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in _SHARES:
        spec = runmod.load_json(os.path.join(_BENCH, "layer_metrics",
                                             name + ".json"))
        assert spec["reducer"] == "region_share"
        assert spec["source"] == "device_trace"
        assert set(spec["args"]) <= {"regions", "phases", "programs"}
        m = listed.get(name)
        if m is not None:
            assert m["layer"] == "model steps" and m["unit"] == "%"
            assert m["source"] == spec["source"]


def test_op_regions_on_a_cpu_trace_of_a_rematted_scan(tmp_path, capsys):
    import jax
    import jax.numpy as jnp

    def loss(w, x):
        def body(h, wl):
            with jax.named_scope("hvd.block.mixer"):
                h = jnp.tanh(h @ wl)
            with jax.named_scope("hvd.block.mlp"):
                h = h + jnp.sin(h @ wl.T)
            return h, None
        h, _ = jax.lax.scan(jax.checkpoint(body), x, w)
        with jax.named_scope("hvd.loss"):
            return jnp.sum(h * h)

    step = jax.jit(jax.grad(loss))
    w, x = jnp.full((3, 64, 64), 0.01), jnp.ones((8, 64))
    compiled = step.lower(w, x).compile()
    step(w, x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            step(w, x).block_until_ready()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    read = op_regions.read_programs(path)
    (name, table), = [(k, v) for k, v in read["programs"].items()
                      if k.startswith("jit_loss(")]
    # the trace's copy of the module says what the compiled text says
    want = {m.group(1): op_regions.region_of(m.group(2)) for m in re.finditer(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*op_name="([^"]*)"',
        compiled.as_text(), re.M)}
    named = {k: v for k, v in want.items() if v[0]}
    assert len(named) > 20
    for instr, (regions, phase) in named.items():
        assert table[instr][:2] == (regions, phase), instr
    ev = op_regions.host_events(path)
    rows = [r for r in op_regions.place(ev["ops"], ev["modules"],
                                        read["programs"])
            if r[0] == "jit_loss"]
    assert len(rows) > 40 and {r[3] for r in rows} <= set(table)
    for program, regions, phase, instr, _ in rows:
        assert (regions, phase) == table[instr][:2]
        if instr in named:
            assert (regions, phase) == named[instr]
    seen = {(r[1][-1], r[2]) for r in rows if r[1]}
    assert {(b, p) for b in ("hvd.block.mixer", "hvd.block.mlp")
            for p in op_regions.PHASES} <= seen
    assert ("hvd.loss", "backward") in seen
    agg = [(p, r, ph, d) for p, r, ph, _, d in rows]
    parts = [op_regions.share(agg, r) for r in (
        [], ["hvd.block.mixer"], ["hvd.block.mlp"], ["hvd.loss"])]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(100.0)
    assert sum(op_regions.share(agg, ["hvd"], [ph])
               for ph in op_regions.PHASES) + parts[0] == pytest.approx(100.0)
    # the operator's command prints the same table
    assert op_regions.main([path]) == 0
    out = capsys.readouterr().out
    assert re.search(r"jit_loss [\d.]+ s: .*hvd\.block\.mlp [\d.]+% "
                     r"\(forward [\d.]+, recompute [\d.]+, backward", out)
    # and with a pattern, where the ops it names lie
    dot = next(i for i, v in named.items()
               if table[i][2] == "dot" and v[1] == "recompute")
    assert op_regions.main([path, re.escape(dot) + "$"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and re.fullmatch(
        r"[\d.]+ s x\d+ jit_loss " + re.escape(dot)
        + r" \[hvd\.block\.\w+ recompute\]", out[0]), out
    assert op_regions.main([]) == 2
