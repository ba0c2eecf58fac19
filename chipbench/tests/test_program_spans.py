"""Tests of what reads the program's own spans; seconds on the CPU.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

``idle_by_span`` on hand-built gaps and spans, and each reducer that reads
``hvd.*`` spans or the names the program gives its programs and kernels,
on ``testdata/hand_spans.json`` with the value worked out beside it.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import program_spans, run as runmod, trace  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
NEW = ["idle_under_prefill_host.backlog", "idle_under_decode_host.backlog",
       "idle_under_emit_deliver.backlog", "paged_table_fill.backlog",
       "prefill_program_ms_per_ktok.backlog",
       "decode_program_ms_p50.backlog", "paged_decode_kernel_share.backlog",
       "flash_kernel_share"]


def hand_red(with_spans=True):
    """The hand-built trace as a reducer sees it."""
    t = json.load(open(os.path.join(BENCH, "testdata", "hand_spans.json")))
    ev = {k: [tuple(e) for e in t[k]] for k in ("ops", "modules", "spans")}
    red = dict(trace.reduce_events(ev), **ev)
    red["hvd_spans"] = [tuple(s) for s in t["hvd_spans"]] \
        if with_spans else []
    return red


def value(metric, red):
    spec = runmod.load_json(os.path.join(BENCH, "layer_metrics",
                                         metric + ".json"))
    reducer = importlib.import_module("chipbench.reducers." + spec["reducer"])
    cell = {"cell": {"name": "hand"}, "spec": spec}
    return reducer.reduce(red, {"max_active": 4}, cell)


@pytest.fixture(autouse=True)
def out_dir(tmp_path, monkeypatch):
    """``program_spans.json`` goes under a directory of the test's own."""
    os.makedirs(tmp_path / "hand")
    monkeypatch.setattr(program_spans, "OUT", str(tmp_path))
    return tmp_path


# -- idle_by_span ---------------------------------------------------------------

def S(name, start, end):
    return (name, start, end - start, {})


def test_idle_gap_that_straddles_two_spans_is_split_by_overlap():
    # gap 1.0-2.0; a covers 0.5-1.3 (0.3 of it), b covers 1.3-2.5 (0.7)
    got = program_spans.idle_by_span(
        [(1.0, 2.0)], [S("a", 0.5, 1.3), S("b", 1.3, 2.5)])
    assert got == {"a": pytest.approx(0.3), "b": pytest.approx(0.7)}
    # trace.name_gaps gives the whole gap to b, which holds the midpoint
    assert trace.name_gaps([(1.0, 2.0)], [("a", 0.5, 0.8), ("b", 1.3, 1.2)]) \
        == {"b": pytest.approx(1.0)}


def test_idle_no_span_is_reported_apart():
    # gap 0-1: a covers 0.2-0.5; 0-0.2 and 0.5-1.0 are covered by nothing
    got = program_spans.idle_by_span([(0.0, 1.0)], [S("a", 0.2, 0.5)])
    assert got == {"a": pytest.approx(0.3),
                   program_spans.NO_SPAN: pytest.approx(0.7)}
    assert program_spans.idle_by_span([(0.0, 1.0)], []) == \
        {program_spans.NO_SPAN: pytest.approx(1.0)}


def test_idle_child_wins_over_its_parent():
    # parent 0-10 with children 2-4 and 6-7; gaps 1-3 and 5-8
    spans = [S("parent", 0, 10), S("kid", 2, 4), S("kid", 6, 7)]
    got = program_spans.idle_by_span([(1, 3), (5, 8)], spans)
    # gap 1-3: parent 1-2, kid 2-3; gap 5-8: parent 5-6 and 7-8, kid 6-7
    assert got == {"parent": pytest.approx(3.0), "kid": pytest.approx(2.0)}
    # a grandchild that ends with its parent, and a span of no length
    spans += [S("grandkid", 3, 4), S("empty", 2.5, 2.5)]
    got = program_spans.idle_by_span([(1, 4)], spans)
    assert got == {"parent": pytest.approx(1.0), "kid": pytest.approx(1.0),
                   "grandkid": pytest.approx(1.0)}


def test_idle_partition_adds_up_and_is_written(out_dir):
    red = hand_red()
    part = program_spans.idle_partition(
        red, {"cell": {"name": "hand"}, "spec": {"args": {}}})
    # window 0-1, busy 0.22 + 0.15 + 0.14 + 0.15 = 0.66
    assert red["busy_s"] == pytest.approx(0.66)
    assert sum(part.values()) == pytest.approx(0.34)
    # 0-0.02, 0.74-0.75, 0.78-0.79, 0.97-0.975 are under no hvd.* span
    assert part[program_spans.NO_SPAN] == pytest.approx(0.045)
    # own time of the parents: step 0.72-0.74 and 0.965-0.97; prefill
    # 0.33-0.34 and 0.975-1.0 (0.32-0.33 is under prefill.fetch)
    assert part["hvd.serve.step"] == pytest.approx(0.025)
    assert part["hvd.serve.prefill"] == pytest.approx(0.035)
    wrote = json.load(open(out_dir / "hand" / "program_spans.json"))
    assert wrote["partition_sum_s"] == pytest.approx(wrote["idle_s"])
    assert wrote["idle_by_span_s"] == pytest.approx(part)
    # no args.causal: nothing says where the host's clock lies
    assert wrote["clock_shift_s"] == {
        "applied": 0.0, "at_least": None, "at_most": None}


# -- the clock ------------------------------------------------------------------

CAUSAL = [["hvd_serve_decode", "hvd.serve.decode.dispatch",
           "hvd.serve.decode.fetch"]]


def ticks(host_late_by):
    """Two decode runs of 46 ms; each dispatched 0.5 ms before it starts
    and fetched 1 ms after it ends, with the host's clock off by
    ``host_late_by``."""
    red = {"devices": [0], "lo": 0.0, "hi": 2.0, "modules": [
        (0, "jit_hvd_serve_decode(3)", 1.000, 0.046),
        (0, "jit_hvd_serve_decode(3)", 1.100, 0.046)]}
    spans = []
    for t in (1.000, 1.100):
        spans += [("hvd.serve.decode.dispatch", t - 0.0005 + host_late_by,
                   0.0002, {}),
                  ("hvd.serve.decode.fetch", t + 0.001 + host_late_by,
                   0.046, {})]
    return red, spans, CAUSAL


def test_clock_is_set_to_the_middle_of_what_cause_and_effect_allow():
    # fetch may close with the run (-1 ms) or dispatch open with it (+0.5)
    shift, lo, hi = program_spans.clock_shift(*ticks(0.0))
    assert (lo, hi) == (pytest.approx(-0.001), pytest.approx(0.0005))
    assert shift == pytest.approx(-0.00025)
    # whatever the host's clock read, the spans land in the same place
    for late_by in (0.002, -0.003):
        red, spans, causal = ticks(late_by)
        assert program_spans.clock_shift(red, spans, causal)[0] == \
            pytest.approx(-0.00025 - late_by)
        # a metric whose spec names the triples reads the spans moved
        red["hvd_spans"] = spans
        moved = program_spans.spans_of(
            red, {"cell": {"name": "hand"},
                  "spec": {"args": {"causal": causal}}})
        assert moved[0][:2] == ("hvd.serve.decode.dispatch",
                                pytest.approx(1.000 - 0.00075))


def test_clock_with_one_bound_or_none():
    only_opens = lambda spans: [s for s in spans
                                if s[0].endswith(".dispatch")]
    # runs start 1.5 ms before their dispatch opens: move that far
    red, spans, causal = ticks(0.002)
    assert program_spans.clock_shift(red, only_opens(spans), causal)[0] == \
        pytest.approx(-0.0015)
    # in order as it stands, and nothing says where in the room it is
    red, spans, causal = ticks(0.0)
    assert program_spans.clock_shift(
        red, only_opens(spans), causal)[0] == 0.0
    # a run far from any span (the trace's edge cut the pair) says nothing
    red["modules"] = [(0, "jit_hvd_serve_decode(3)", 1.5, 0.046)]
    assert program_spans.clock_shift(red, spans, causal) == (
        0.0, float("-inf"), float("inf"))
    # and so do spans with no triple to hold them to
    assert program_spans.clock_shift(*ticks(0.0)[:2], []) == (
        0.0, float("-inf"), float("inf"))


def test_clock_that_cause_and_effect_contradict_is_not_placed(out_dir, capsys):
    # the fetch closes 1 ms before its run ends and the dispatch opens
    # 0.5 ms after its run starts: at least +1 ms, at most -0.5 ms
    red, spans, causal = ticks(0.0)
    spans = [(n, t + (0.001 if n.endswith(".dispatch") else -0.002), d, a)
             for n, t, d, a in spans]
    assert program_spans.clock_shift(red, spans, causal) is None
    red["hvd_spans"] = spans
    at = program_spans.placed(red, {"cell": {"name": "hand"},
                                    "spec": {"args": {"causal": causal}}})
    assert at["clock"] is None and at["spans"] == spans
    assert "stay where the profiler put them" in capsys.readouterr().err


# -- the reducers, each on the hand-built trace --------------------------------

WORKED = {
    # admit 0.02-0.06 and 0.79-0.795 (0.045), prefill.dispatch 0.06-0.10
    "idle_under_prefill_host.backlog": 8.5,
    # prefill.fetch 0.32-0.33, grow 0.34-0.35, tables 0.35-0.37, decode
    # dispatch 0.37-0.39 and 0.795-0.80
    "idle_under_decode_host.backlog": 6.5,
    # decode.fetch 0.39-0.40, 0.55-0.56, 0.70-0.705, 0.95-0.955 (0.03);
    # emit 0.705-0.72, 0.955-0.965 (0.025); deliver 0.75-0.78
    "idle_under_emit_deliver.backlog": 8.5,
    # blocks 5 + 7 over max_active 4 x (n_cols 4 + 8)
    "paged_table_fill.backlog": 100 * 12 / 48,
    # the one prefill run, 0.20 s, inside the span of 2,000 tokens; the
    # span of 1,000 tokens at 0.975 holds no run and is left out
    "prefill_program_ms_per_ktok.backlog": 200 / 2.0,
    # decode runs of 300 and 150 ms
    "decode_program_ms_p50.backlog": 225.0,
    # the kernel 0.15 + 0.10 s of 0.66 s busy; the fusion that names it
    # as its operand is not the kernel
    "paged_decode_kernel_share.backlog": 100 * 0.25 / 0.66,
}


@pytest.mark.parametrize("metric", sorted(WORKED))
def test_reducer_on_the_hand_built_spans(metric):
    assert value(metric, hand_red()) == pytest.approx(WORKED[metric])


def test_flash_kernel_share_finds_the_three_names():
    red = hand_red()
    call = "%{} = bf16[4,2048,128]{{2,1,0}} custom-call(%q), " \
           'custom_call_target="tpu_custom_call"'
    red["ops"] = red["ops"] + [        # named as autodiff names them
        (0, call.format("jvp_hvd_flash_fwd_.1"), 0.10, 0.02),
        (0, call.format("transpose_jvp_hvd_flash_bwd_dq__.2"), 0.12, 0.03),
        (0, call.format("transpose_jvp_hvd_flash_bwd_dkv__.3"), 0.15, 0.05)]
    # 0.02 + 0.03 + 0.05 of 0.66 s busy; the paged kernel is not counted
    assert value("flash_kernel_share", red) == pytest.approx(100 * 0.1 / 0.66)


@pytest.mark.parametrize("metric", NEW)
def test_reducer_finds_nothing_in_a_program_without_the_names(metric):
    """An older commit: no hvd.* span, programs jit__unknown, kernels
    unnamed.  The line leaves the metric out; nothing raises."""
    red = hand_red(with_spans=False)
    red["modules"] = [(dv, "jit__unknown(9)", t, d)
                      for dv, _, t, d in red["modules"]]
    red["ops"] = [(dv, n.replace("hvd_paged_decode", "closed_call"), t, d)
                  for dv, n, t, d in red["ops"]]
    assert value(metric, red) is None


def test_spans_are_read_from_the_newest_xplane_file(out_dir):
    """``spans_of`` on a real trace of two annotations, one with
    attributes, written by this process."""
    import jax
    trace_dir = out_dir / "hand" / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("hvd.test.outer", n=3) as sp:
        with jax.profiler.TraceAnnotation("chipbench.not_ours"):
            sp.set_metadata(m=4)
    jax.profiler.stop_trace()
    red = {"devices": [0], "modules": [], "lo": 0.0, "hi": 0.0}
    at = program_spans.placed(red, {"cell": {"name": "hand"},
                                    "spec": {"args": {}}})
    assert [(n, a) for n, _, _, a in at["spans"]] == \
        [("hvd.test.outer", {"n": 3, "m": 4})]
    assert at["spans"][0][2] > 0 and at["clock"][0] == 0.0
