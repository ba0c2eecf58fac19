"""Auxiliary subsystems: checkpoint/resume, elastic sampler, join, grouped
async, autotuner unit behavior.
"""

import numpy as np
import pytest

import horovod_tpu as hvd


# ---------------------------------------------------------------------------
# checkpoint (orbax)
# ---------------------------------------------------------------------------

def test_checkpoint_save_restore_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp
    from horovod_tpu.utils.checkpoint import Checkpointer
    ckpt = Checkpointer(str(tmp_path / "ck"))
    tree = {"params": {"w": jnp.arange(8.0), "b": jnp.ones((3,))},
            "step": jnp.int32(7)}
    ckpt.save(7, tree)
    assert ckpt.latest_step() == 7
    restored = ckpt.restore()
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.arange(8.0))
    assert int(restored["step"]) == 7
    ckpt.close()


def test_checkpoint_resharded_restore(tmp_path):
    """Restore onto an explicit sharding target — the elastic-restart path
    (new mesh after membership change)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.utils.checkpoint import Checkpointer
    mesh = hvd.mesh()
    ckpt = Checkpointer(str(tmp_path / "ck2"))
    tree = {"w": jnp.arange(16.0)}
    ckpt.save(0, tree)
    target = {"w": jax.ShapeDtypeStruct(
        (16,), jnp.float32, sharding=NamedSharding(mesh, P("hvd")))}
    restored = ckpt.restore(target=target)
    assert restored["w"].sharding == NamedSharding(mesh, P("hvd"))
    np.testing.assert_allclose(np.asarray(restored["w"]), np.arange(16.0))
    ckpt.close()


def test_checkpoint_max_to_keep(tmp_path):
    import jax.numpy as jnp
    from horovod_tpu.utils.checkpoint import Checkpointer
    ckpt = Checkpointer(str(tmp_path / "ck3"), max_to_keep=2)
    for s in range(4):
        ckpt.save(s, {"x": jnp.float32(s)})
    assert ckpt.all_steps() == [2, 3]
    ckpt.close()


def test_checkpoint_restore_missing(tmp_path):
    from horovod_tpu.utils.checkpoint import Checkpointer
    ckpt = Checkpointer(str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError):
        ckpt.restore()
    ckpt.close()


# ---------------------------------------------------------------------------
# elastic sampler († test_torch_elastic.py sampler cases)
# ---------------------------------------------------------------------------

def test_sampler_shards_evenly():
    from horovod_tpu.elastic import ElasticSampler
    samplers = []
    for r in range(4):
        s = ElasticSampler(100, shuffle=False)
        s.set_rank_size(r, 4)
        samplers.append(list(s))
    all_idx = sorted(i for s in samplers for i in s)
    assert all_idx == list(range(100))
    assert all(len(s) == 25 for s in samplers)


def test_sampler_reshards_remaining_after_membership_change():
    from horovod_tpu.elastic import ElasticSampler
    s = ElasticSampler(20, shuffle=False)
    s.set_rank_size(0, 2)
    first_half = list(s)[:5]
    s.record_batch(first_half)
    # World shrinks to 1: remaining indices = all except processed.
    s.set_rank_size(0, 1)
    remaining = list(s)
    assert set(remaining) == set(range(20)) - set(first_half)


def test_sampler_epoch_resets_progress():
    from horovod_tpu.elastic import ElasticSampler
    s = ElasticSampler(10, shuffle=True, seed=1)
    s.record_batch([0, 1, 2])
    s.set_epoch(1)
    assert len(s) == 10
    # Shuffle differs across epochs.
    e1 = list(s)
    s.set_epoch(2)
    assert list(s) != e1


def test_sampler_state_dict_roundtrip():
    from horovod_tpu.elastic import ElasticSampler
    s = ElasticSampler(10, shuffle=False)
    s.record_batch([1, 3])
    sd = s.state_dict()
    s2 = ElasticSampler(10, shuffle=False)
    s2.load_state_dict(sd)
    assert set(s2) == set(range(10)) - {1, 3}


# ---------------------------------------------------------------------------
# join + grouped async
# ---------------------------------------------------------------------------

def test_join_returns_last_rank():
    assert hvd.join() == hvd.size() - 1


def test_grouped_allreduce_async():
    xs = [hvd.per_rank_from_fn(
        lambda r, i=i: np.full((4,), float(r + i), np.float32))
        for i in range(3)]
    handles = hvd.grouped_allreduce_async(xs, hvd.Average, name="grp")
    for i, h in enumerate(handles):
        got = hvd.to_numpy(hvd.synchronize(h))
        np.testing.assert_allclose(got, np.full((4,), 3.5 + i), rtol=1e-6)


def test_grouped_allreduce_sync():
    xs = [hvd.per_rank_from_fn(
        lambda r, i=i: np.full((2,), float(r * i), np.float32))
        for i in range(2)]
    outs = hvd.grouped_allreduce_sync(xs, hvd.Sum)
    np.testing.assert_allclose(hvd.to_numpy(outs[0]), 0.0)
    np.testing.assert_allclose(hvd.to_numpy(outs[1]), np.full((2,), 28.0))


# ---------------------------------------------------------------------------
# autotuner unit behavior († parameter_manager tests)
# ---------------------------------------------------------------------------

def test_autotuner_proposes_and_converges(tmp_path):
    from horovod_tpu.utils.autotune import Autotuner

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.config = config_mod.Config(
        autotune=True, autotune_log=str(tmp_path / "at.log"),
        autotune_warmup_samples=1, autotune_steps_per_sample=2)
    at = Autotuner(st)
    # Feed cycles: throughput peaks at larger thresholds.
    for i in range(200):
        if at._done:
            break
        t, c, m, s, h, b = at._current
        score_bias = 1.0 + (np.log2(t) - 20) * 0.1
        at.record_cycle(int(1e6 * score_bias), 0.001)
    log = (tmp_path / "at.log").read_text()
    assert "sample #" in log
    # Knobs were mutated by the proposals.
    assert (st.config.fusion_threshold, st.config.cycle_time_ms) != (
        64 * 1024 * 1024, 5.0) or at._done


def test_autotuner_commits_exact_grid_values(tmp_path):
    """Regression: the converged knobs must be EXACT candidate-grid
    values.  The old ``_raw`` reconstructed them as ``2 ** log2(x)`` from
    the normalized GP samples, which drifted the committed cycle time off
    the grid (2.5 -> 2.4999999999999996).  The 4th (schedule) dimension
    joins the same assertion so the knob-space growth cannot reintroduce
    the drift through a new code path."""
    from horovod_tpu.utils.autotune import (
        Autotuner, _CYCLE_TIMES, _sched_arms, _THRESHOLDS, _WIRE_MODES)

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.config = config_mod.Config(
        autotune=True, autotune_warmup_samples=0,
        autotune_steps_per_sample=1, cycle_time_ms=2.5)
    at = Autotuner(st)
    rng = np.random.RandomState(0)
    for i in range(400):
        if at._done:
            break
        # Flat-ish noisy scores: convergence picks SOME sampled config.
        at.record_cycle(int(1e6 + rng.randint(0, 1000)), 0.001)
    assert at._done, "tuner never converged"
    t, c, m, s, h, b = at._current
    assert t in _THRESHOLDS or t == st.config.fusion_threshold
    assert st.config.fusion_threshold == t
    # The drift bug showed up in the float knob: exact membership now.
    assert c in _CYCLE_TIMES or c == 2.5
    assert st.config.cycle_time_ms == c
    assert m in _WIRE_MODES
    assert st.config.wire_precision == m
    arms = _sched_arms()
    assert s in arms
    if s == "monolithic":
        assert st.config.sched_mode == "monolithic"
    elif s.startswith("compiled:"):
        assert st.config.sched_mode == "compiled"
        assert f"compiled:rs_ag:{st.config.sched_chunks}" == s
    else:
        assert st.config.sched_mode == "decomposed"
        assert f"rs_ag:{st.config.sched_chunks}" == s
    assert b in at._buckets
    assert st.config.bucket_bytes == b
    # Every recorded sample keeps exact raw knobs alongside the GP coords
    # — all six of them, so neither the hierarchy nor the bucket-cap
    # dimension can reintroduce the round-trip drift.
    for (rt, rc, rm, rs, rh, rb), (xt, xc, xm, xs, xh, xb) in zip(
            at._samples_raw, at._samples_X):
        assert rt in _THRESHOLDS or rt == 64 * 1024 * 1024
        assert rc in _CYCLE_TIMES or rc == 2.5
        assert rs in arms
        assert rh in at._hiers
        assert rb in at._buckets
        assert 2.0 ** xt == pytest.approx(rt)


def test_autotune_sched_arms_track_lowering_modes():
    """Regression for the arm-set drift bug: the tuner's schedule arms
    used to be a hand-maintained list disjoint from ``lower.SCHED_MODES``
    (it searched ``rs_ag:*`` strings while the config validator accepted
    a different vocabulary).  The arms are now DERIVED from SCHED_MODES;
    this test pins the sync so a new sched mode cannot ship without an
    autotune arm, and every generated arm round-trips through the
    resolver's descriptor parsers and ``_apply``."""
    from horovod_tpu.ops.sched import known_descriptor
    from horovod_tpu.ops.sched.lower import (SCHED_MODES,
                                             autotune_sched_arms)
    from horovod_tpu.utils.autotune import _SCHED_CHUNK_COUNTS, _sched_arms

    arms = _sched_arms()
    assert arms == autotune_sched_arms(_SCHED_CHUNK_COUNTS)
    # Every declared sched mode contributes at least one arm...
    assert "monolithic" in SCHED_MODES and "monolithic" in arms
    for k in _SCHED_CHUNK_COUNTS:
        assert ("decomposed" not in SCHED_MODES) or f"rs_ag:{k}" in arms
        assert ("compiled" not in SCHED_MODES) \
            or f"compiled:rs_ag:{k}" in arms
    # ...and no arm exists the engine's resolver cannot parse.
    for a in arms:
        assert a == "monolithic" or known_descriptor(a), a
    # _apply commits every arm to a config the validator accepts.
    from horovod_tpu import config as config_mod

    class FakeState:
        pass

    from horovod_tpu.utils.autotune import Autotuner
    st = FakeState()
    st.config = config_mod.Config(autotune=True, autotune_warmup_samples=0,
                                  autotune_steps_per_sample=1)
    at = Autotuner(st)
    for a in arms:
        at._apply(1 << 20, 1.0, "fp32", a, "flat")
        assert st.config.sched_mode in SCHED_MODES
        if a.startswith("compiled:"):
            assert st.config.sched_mode == "compiled"
        elif a == "monolithic":
            assert st.config.sched_mode == "monolithic"
        else:
            assert st.config.sched_mode == "decomposed"


def test_autotuner_discards_settle_cycles_after_commit(tmp_path):
    """A knob commit pays XLA compiles on its first cycles — new fused
    signatures, and on the compiled-schedule arms a whole new program.
    Those cycles must be discarded, not scored: counting them grades the
    warm incumbent against cold challengers, and the tuner converges
    right back onto the (deliberately bad) starting knobs because every
    challenger's window is poisoned by its own compile stall."""
    from horovod_tpu.utils.autotune import _SETTLE_CYCLES, Autotuner

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.config = config_mod.Config(autotune=True, autotune_warmup_samples=0,
                                  autotune_steps_per_sample=1)
    at = Autotuner(st)
    at.record_cycle(1000, 0.001)  # sample #1 -> propose -> _apply
    assert at._settle_left == _SETTLE_CYCLES
    n = len(at._samples_y)
    # The settle window: a compile-stalled outlier cycle must vanish
    # without being accumulated or recorded as a sample.
    for _ in range(_SETTLE_CYCLES):
        at.record_cycle(10 ** 12, 5.0)
    assert len(at._samples_y) == n
    assert at._settle_left == 0
    assert at._acc_cycles == 0 and at._acc_bytes == 0
    # Scoring resumes on the next cycle, clean of the stall.
    at.record_cycle(1000, 0.001)
    assert len(at._samples_y) == n + 1
    assert max(at._samples_y) == pytest.approx(1000 / 0.001)
    # Zero-payload cycles never consume the settle window (an idle cycle
    # compiles nothing, so it proves nothing about warmth).
    at._settle_left = _SETTLE_CYCLES
    at.record_cycle(0, 0.001)
    assert at._settle_left == _SETTLE_CYCLES


def test_autotuner_pins_compiled_sched_when_distributed():
    """Compiled default + multi-process engine: the schedule dimension
    pins to the compiled descriptor (same rank-divergence rule as the
    decomposed pin below)."""
    from horovod_tpu.utils.autotune import Autotuner

    class FakeEngine:
        distributed = True

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.engine = FakeEngine()
    st.config = config_mod.Config(
        autotune=True, autotune_warmup_samples=0,
        autotune_steps_per_sample=1, sched_mode="compiled", sched_chunks=2)
    at = Autotuner(st)
    assert at._scheds == ["compiled:rs_ag:2"]
    assert {g[3] for g in at._grid_raw} == {"compiled:rs_ag:2"}


def test_autotuner_pins_sched_and_mode_when_distributed():
    """Multi-process engines must pin BOTH the wire-precision and the
    schedule dimensions to the configured defaults: a per-rank commit of
    either diverges the enqueue-time resolution across processes (hang).
    """
    from horovod_tpu.utils.autotune import Autotuner

    class FakeEngine:
        distributed = True

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.engine = FakeEngine()
    st.config = config_mod.Config(
        autotune=True, autotune_warmup_samples=0,
        autotune_steps_per_sample=1, wire_precision="int8",
        sched_mode="decomposed", sched_chunks=2)
    at = Autotuner(st)
    assert at._modes == ["int8"]
    assert at._scheds == ["rs_ag:2"]
    assert at._hiers == ["flat"]
    # And every grid candidate keeps them fixed.
    assert {g[2] for g in at._grid_raw} == {"int8"}
    assert {g[3] for g in at._grid_raw} == {"rs_ag:2"}
    assert {g[4] for g in at._grid_raw} == {"flat"}
    # The bucket cap stays SEARCHABLE even when distributed: like the
    # fusion threshold it only shapes the local cycle thread's grouping.
    assert {g[5] for g in at._grid_raw} == set(at._buckets)
    assert len(at._buckets) > 1


def test_autotuner_hierarchy_dimension():
    """The 5th knob: a detected topology split enters the search as
    tier:<n_local> (plus its half), _apply commits the hierarchical
    config knobs, and distributed engines pin to the configured default.
    """
    from horovod_tpu.utils.autotune import Autotuner

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.size = 8
    st.local_size = 8
    st.config = config_mod.Config(
        autotune=True, autotune_warmup_samples=0,
        autotune_steps_per_sample=1, local_size_env=4)
    at = Autotuner(st)
    assert at._hiers == ["flat", "tier:4", "tier:2"]
    # The analytic decision table seeds the search (perfmodel).
    assert at.split_table and {r["split"] for r in at.split_table} <= {
        "flat", "hier"}
    at._apply(1 << 20, 1.0, "fp32", "monolithic", "tier:2")
    assert st.config.hierarchical_allreduce
    assert st.config.hierarchical_local_size == 2
    at._apply(1 << 20, 1.0, "fp32", "monolithic", "flat")
    assert not st.config.hierarchical_allreduce
    # Distributed + flag on: pinned to the configured tier, never "flat".
    class FakeEngine:
        distributed = True
    st2 = FakeState()
    st2.size = 8
    st2.engine = FakeEngine()
    st2.config = config_mod.Config(
        autotune=True, hierarchical_allreduce=True,
        hierarchical_local_size=4)
    at2 = Autotuner(st2)
    assert at2._hiers == ["tier:4"]
    assert {g[4] for g in at2._grid_raw} == {"tier:4"}


def test_autotuner_bucket_bytes_dimension():
    """The 6th knob: bucket cap candidates include 0 (uncapped) plus the
    grid caps, an off-grid configured cap joins the search, and _apply
    commits ``config.bucket_bytes`` (which the engine folds into its
    fusion grouping and the backward bucketer reads as its size target).
    """
    from horovod_tpu.utils.autotune import _BUCKET_BYTES, Autotuner

    class FakeState:
        pass

    from horovod_tpu import config as config_mod
    st = FakeState()
    st.config = config_mod.Config(
        autotune=True, autotune_warmup_samples=0,
        autotune_steps_per_sample=1, bucket_bytes=7 << 20)
    at = Autotuner(st)
    assert at._buckets == list(_BUCKET_BYTES) + [7 << 20]
    assert 0 in at._buckets
    assert at._current[5] == 7 << 20
    at._apply(1 << 20, 1.0, "fp32", "monolithic", "flat", 4 << 20)
    assert st.config.bucket_bytes == 4 << 20
    at._apply(1 << 20, 1.0, "fp32", "monolithic", "flat", 0)
    assert st.config.bucket_bytes == 0
    # Default-arg form (legacy 5-knob callers) commits the uncapped arm.
    at._apply(1 << 20, 1.0, "fp32", "monolithic", "flat")
    assert st.config.bucket_bytes == 0


@pytest.mark.integration
def test_autotune_improves_dispatch_bound_throughput(tmp_path):
    """The GP+EI loop, started from a deliberately bad (threshold,
    cycle-time) point on a dispatch-bound gradient stream, runs to its
    end and moves off the start.  What is asserted is exact: the exit
    code and the knob.  The bench's wall-clock ``speedup`` is a CPU
    timing and gates nothing (ROADMAP North star)."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "autotune_bench.py"),
         "--log", str(tmp_path / "autotune_log.txt"), "--no-persist"],
        capture_output=True, text=True, timeout=800, cwd=repo)
    assert res.returncode == 0, res.stdout + res.stderr
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    # The tuner must have moved off the bad 4 KB threshold.
    assert rec["tuned"]["knobs"]["fusion_threshold"] > 4096, rec
