"""Launcher: host parsing, rank assignment, end-to-end multi-process jobs.

Mirrors † ``test/single/test_run.py`` (arg/host parsing, command
construction) and † ``test/integration/test_static_run.py`` (really exec the
launcher end-to-end on localhost).
"""

import os
import subprocess
import sys
import time

import pytest

from horovod_tpu.runner import parse_hosts
from horovod_tpu.runner.hosts import assign_ranks
from horovod_tpu.runner.launch import build_parser, _knob_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# parsing
# ---------------------------------------------------------------------------

def test_parse_hosts():
    hs = parse_hosts("a:2,b:4")
    assert [(h.hostname, h.slots) for h in hs] == [("a", 2), ("b", 4)]
    assert parse_hosts("solo")[0].slots == 1


@pytest.mark.parametrize("bad", ["", ":3", "h:x", "h:0"])
def test_parse_hosts_bad(bad):
    with pytest.raises(ValueError):
        parse_hosts(bad)


def test_assign_ranks():
    hs = parse_hosts("a:2,b:2")
    assert assign_ranks(hs, 3) == [(0, "a", 0), (1, "a", 1), (2, "b", 0)]
    with pytest.raises(ValueError):
        assign_ranks(hs, 5)


def test_cli_knob_env():
    args = build_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "8", "--cycle-time-ms", "2.5",
         "--autotune", "--log-level", "debug", "--", "python", "x.py"])
    env = _knob_env(args)
    assert env["HVDTPU_FUSION_THRESHOLD"] == str(8 * 1024 * 1024)
    assert env["HVDTPU_CYCLE_TIME"] == "2.5"
    assert env["HVDTPU_AUTOTUNE"] == "1"
    assert env["HVDTPU_LOG_LEVEL"] == "debug"


def test_cli_platform_knob(monkeypatch, tmp_path):
    args = build_parser().parse_args(
        ["-np", "2", "--platform", "cpu", "--", "python", "x.py"])
    assert _knob_env(args)["HVDTPU_PLATFORM"] == "cpu"
    import horovod_tpu.config as config_mod
    monkeypatch.setenv("HVDTPU_PLATFORM", "CPU")  # normalized, not passed raw
    assert config_mod.from_env().platform == "cpu"
    monkeypatch.setenv("HVDTPU_PLATFORM", "gpu")  # fails at the knob, not jax
    with pytest.raises(ValueError):
        config_mod.from_env()
    monkeypatch.delenv("HVDTPU_PLATFORM")
    cfgf = tmp_path / "c.yaml"
    cfgf.write_text("platform: banana\n")
    with pytest.raises(ValueError):
        config_mod.from_yaml(str(cfgf))
    cfgf.write_text("platform: TPU\n")
    assert config_mod.from_yaml(str(cfgf)).platform == "tpu"


def test_cli_config_file(tmp_path):
    cfg = tmp_path / "c.yaml"
    cfg.write_text("cycle_time_ms: 7.5\nautotune: true\n")
    args = build_parser().parse_args(
        ["-np", "1", "--config-file", str(cfg), "--", "true"])
    env = _knob_env(args)
    assert env["HVDTPU_CYCLE_TIME"] == "7.5"
    assert env["HVDTPU_AUTOTUNE"] == "1"


# ---------------------------------------------------------------------------
# end-to-end († test_static_run)
# ---------------------------------------------------------------------------

def _hvdrun(np_, script_args, timeout=240, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_), "--",
         sys.executable] + script_args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.integration
@pytest.mark.parametrize("np_", [2, 8])
def test_hvdrun_collective_battery(np_):
    """The full verb battery over real negotiated transport — at the
    historical 2-process rig and at np=8 (controller round-barrier,
    fused grouped dispatch, ragged allgatherv, non-uniform alltoallv)."""
    res = _hvdrun(np_, [os.path.join(REPO, "tests", "mp_train_worker.py")],
                  timeout=120 + 30 * np_)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(np_):
        assert f"rank {r}: OK" in res.stdout, res.stdout


@pytest.mark.integration
def test_hvdrun_worker_failure_kills_job():
    code = ("import sys, os; "
            "sys.exit(3 if os.environ['HVDTPU_CROSS_RANK'] == '1' else 0)")
    res = _hvdrun(2, ["-c", code])
    assert res.returncode == 3


@pytest.mark.integration
def test_hvdrun_no_command():
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "1"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert res.returncode == 2
    assert "no command" in res.stderr


@pytest.mark.integration
@pytest.mark.parametrize("np_", [2, 4])
def test_hvdrun_quantized_allreduce_parity(np_):
    """Block-scaled int8/fp8/bf16 wire modes over real negotiated
    transport: parity within the documented tolerance at np=2 (the
    ci.yaml quantized-parity job) and np=4, plus mixed-mode fusion-group
    consistency across processes (divergent groups would hang, so
    completion is the assertion)."""
    res = _hvdrun(np_, [os.path.join(REPO, "tests", "mp_quant_worker.py")],
                  timeout=120 + 30 * np_)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(np_):
        assert f"rank {r}: QUANT-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.parametrize("np_", [2, 4])
def test_hvdrun_decomposed_allreduce_parity(np_):
    """Decomposed (ops/sched) vs monolithic allreduce over real
    negotiated transport: BIT-exact for int8/fp8 at both np=2 (the
    ci.yaml decomposed-parity job) and np=4, BIT-exact for fp32 at np=2
    and <=2-ulp at np=4 (ring association order — see the worker
    docstring), plus mixed-schedule fusion-group consistency and the
    join/rebuild path (a joined rank reconstructs the chunked program
    from the meta's ``sc`` field; divergence hangs, so completion is
    part of the assertion)."""
    res = _hvdrun(np_, [os.path.join(REPO, "tests", "mp_sched_worker.py")],
                  timeout=120 + 30 * np_)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(np_):
        assert f"rank {r}: SCHED-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.parametrize("np_", [2, 4])
def test_hvdrun_compiled_allreduce_parity(np_):
    """Compiled single-program (ops/sched/compiled) vs monolithic
    allreduce over real negotiated transport (the ci.yaml
    compiled-parity job): BIT-exact for int8/fp8 at both sizes,
    BIT-exact for fp32 at np=2 and <=2-ulp at np=4, with the engine's
    per-chunk dispatch counter pinned at ZERO for the whole battery
    (one cached jitted program per fused group), a mixed-mode phase
    where a decomposed-pinned rank adopts the coordinator's echoed
    compiled descriptor before fusion (divergent backends deadlock on
    per-executable channel IDs, so completion is part of the
    assertion), and the join/rebuild path with a compiled ``sc``
    descriptor."""
    res = _hvdrun(np_, [os.path.join(REPO, "tests", "mp_sched_worker.py")],
                  timeout=120 + 30 * np_,
                  extra_env={"HVDTPU_TEST_MODE": "compiled"})
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(np_):
        assert f"rank {r}: COMPILED-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.parametrize("np_", [2, 4])
def test_hvdrun_zero1_parity(np_):
    """ZeRO-1 sharded-optimizer wire pattern and the bucketed backward
    path over real negotiated transport (the ci.yaml zero1-parity job):
    reduce-scatter -> 1/n local update -> parameter allgather matches
    the dense allreduce step BIT-exact at np=2 / <=2-ulp at np=4;
    bucketed vs unbucketed eager reduction bit-exact for fp32 AND int8
    (block-aligned entries keep quant scales identical under
    regrouping); the compiled bucketed pass rides the single-program
    backend with zero new per-chunk dispatches; and the join/rebuild
    path runs through the bucketed enqueue+nudge loop."""
    res = _hvdrun(np_, [os.path.join(REPO, "tests", "mp_sched_worker.py")],
                  timeout=120 + 30 * np_,
                  extra_env={"HVDTPU_TEST_MODE": "zero"})
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(np_):
        assert f"rank {r}: ZERO-OK" in res.stdout, res.stdout


@pytest.mark.integration
def test_hvdrun_hierarchical_parity():
    """Chunked+tiered (``hier:2:2``) vs flat allreduce over real
    negotiated transport at np=4 as a 2x2 tier mesh (the ci.yaml
    hierarchical-parity job): int8 BIT-exact, fp8 bounded (fp16
    accumulator — see the worker docstring), fp32 <=2-ulp, a quantized
    cross-tier hop under an fp32 fast tier, mixed flat+tiered fusion
    groups, the join/rebuild path with a tiered ``sc`` descriptor, and
    rank-labeled ``hvd_perf_tier_*`` gauges on ``/cluster``.  A
    dispatch-counter guard inside the worker proves the tiered executor
    ran (a silent flat fallback would make parity vacuous)."""
    res = _hvdrun(4, [os.path.join(REPO, "tests", "mp_sched_worker.py")],
                  timeout=360,
                  extra_env={"HVDTPU_TEST_MODE": "hier",
                             "HVDTPU_HIERARCHICAL_LOCAL_SIZE": "2"})
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(4):
        assert f"rank {r}: HIER-OK" in res.stdout, res.stdout


@pytest.mark.integration
def test_hvdrun_join_uneven_inputs():
    """† test_horovod_join: rank 0 runs 3 steps, rank 1 runs 5; the job
    completes (no deadlock) and surviving-step allreduces are correct."""
    res = _hvdrun(2, [os.path.join(REPO, "tests", "mp_join_worker.py")])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 0: JOIN-OK last=1" in res.stdout
    assert "rank 1: JOIN-OK last=1" in res.stdout


@pytest.mark.integration
def test_hvdrun_np4_grouped_and_process_set():
    """Round-2 verdict #5: the fused grouped path and a process-set
    collective over real negotiated transport at np=4 (the controller's
    round-barrier beyond the 2-rank world)."""
    res = _hvdrun(4, [os.path.join(REPO, "tests", "mp_np4_worker.py")])
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(4):
        assert f"rank {r}: NP4-OK" in res.stdout, res.stdout


@pytest.mark.integration
def test_hvdrun_np4_stall_detection():
    """One rank diverges (never submits); every submitting rank must get
    the stall warning + HorovodInternalError shutdown while the diverged
    rank exits cleanly († stall_inspector.cc semantics at np=4)."""
    res = _hvdrun(4, [os.path.join(REPO, "tests", "mp_np4_worker.py")],
                  extra_env={
                      "HVDTPU_TEST_MODE": "stall",
                      "HVDTPU_STALL_CHECK_TIME_SECONDS": "2",
                      "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS": "4",
                  })
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(3):
        assert f"rank {r}: STALL-ERR-OK" in res.stdout, res.stdout
    assert "rank 3: STALL-BYSTANDER-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.slow  # tier-1 budget: covered by CI multiprocess-e2e
def test_hvdrun_sync_batch_norm():
    """† sync_batch_norm semantics over 2 real processes with different
    shards, against a concatenated-batch BatchNorm oracle."""
    res = _hvdrun(2, [os.path.join(REPO, "tests", "mp_sync_bn_worker.py")])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 0: SYNC-BN-OK" in res.stdout
    assert "rank 1: SYNC-BN-OK" in res.stdout


@pytest.mark.integration
@pytest.mark.slow  # tier-1 budget: covered by CI multiprocess-e2e
def test_hvdrun_torch_distributed_optimizer():
    """†3.2: the torch hot path over 2 real processes with different data."""
    res = _hvdrun(2, [os.path.join(REPO, "tests", "mp_torch_worker.py")])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 0: TORCH-OK" in res.stdout
    assert "rank 1: TORCH-OK" in res.stdout


@pytest.mark.integration
def test_hvdrun_elastic_kill_blacklist_relaunch(tmp_path):
    """† test/integration/elastic: full elastic circle through the CLI.

    np=2 via a discovery script naming two 'hosts' (localhost and
    127.0.0.1 — distinct for blacklisting, both exec'd locally); rank 1
    hard-crashes at step 3; the ElasticDriver must blacklist its host,
    relaunch at np=1, and the survivor must resume from the last
    state.commit() with exact value continuity (w follows
    ``w <- size*(w+1)``: 2,6,14 at np=2, then 15,16,17 at np=1)."""
    discover = tmp_path / "discover.sh"
    discover.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.1:1\n")
    discover.chmod(0o755)
    state = tmp_path / "state.json"
    log = tmp_path / "train.log"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HVDTPU_TEST_STATE"] = str(state)
    env["HVDTPU_TEST_LOG"] = str(log)
    env["HVDTPU_TEST_KILL"] = "1"
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", str(discover), "--",
         sys.executable, os.path.join(REPO, "tests", "mp_elastic_worker.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    lines = log.read_text().splitlines()
    assert "START rank=0 size=2 resume_step=0 w=0.0" in lines
    assert "CRASH rank=1 step=3" in lines
    # Relaunched at np=1 from the last commit (step 3, w=14), not from 0.
    assert "START rank=0 size=1 resume_step=3 w=14.0" in lines
    assert "DONE rank=0 size=1 step=6 w=17.0" in lines
    import json as _json
    final = _json.loads(state.read_text())
    assert final == {"step": 6, "w": 17.0}


@pytest.mark.integration
@pytest.mark.slow  # tier-1 budget (~21s grow circle): CI multiprocess-e2e runs it
def test_hvdrun_elastic_grow_uses_new_host(tmp_path):
    """Scale-UP circle: the job starts at np=1; mid-run the discovery
    file gains a second host; the driver's growth watcher bumps the
    membership epoch, the worker exits with the restart code at its next
    commit, and the driver relaunches at np=2 — resuming from the last
    commit (at size 1, w == step exactly) with both ranks training."""
    hostsfile = tmp_path / "hosts.txt"
    hostsfile.write_text("localhost:1\n")
    discover = tmp_path / "discover.sh"
    discover.write_text(f"#!/bin/sh\ncat {hostsfile}\n")
    discover.chmod(0o755)
    state = tmp_path / "state.json"
    log = tmp_path / "train.log"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HVDTPU_TEST_STATE"] = str(state)
    env["HVDTPU_TEST_LOG"] = str(log)
    env["HVDTPU_TEST_TOTAL"] = "40"
    env["HVDTPU_TEST_STEP_DELAY"] = "0.4"
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "1",
         "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", str(discover), "--",
         sys.executable, os.path.join(REPO, "tests", "mp_elastic_worker.py")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    try:
        # Let the np=1 incarnation commit a few steps, then add capacity.
        deadline = time.time() + 120
        while time.time() < deadline:
            if log.exists() and sum(
                    1 for ln in log.read_text().splitlines()
                    if ln.startswith("STEP")) >= 3:
                break
            time.sleep(0.5)
        hostsfile.write_text("localhost:1\n127.0.0.1:1\n")
        out, _ = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out
    lines = log.read_text().splitlines()
    assert "START rank=0 size=1 resume_step=0 w=0.0" in lines
    # The relaunch runs at size 2 and resumed from the exact commit
    # (w == step at size 1).
    restart = [ln for ln in lines
               if ln.startswith("START rank=0 size=2 resume_step=")]
    assert restart, lines
    resumed = restart[0].split("resume_step=")[1].split()
    assert float(resumed[1].split("=")[1]) == float(resumed[0]) > 0
    assert any(ln.startswith("STEP rank=1 size=2") for ln in lines), lines
    assert any(ln.startswith("DONE rank=0 size=2 step=40") for ln in lines)
    import json as _json
    assert _json.loads(state.read_text())["step"] == 40


@pytest.mark.integration
def test_hvdrun_elastic_flags_require_discovery():
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--min-np", "1", "--", "python", "x.py"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert res.returncode == 2
    assert "host-discovery-script" in res.stderr


@pytest.mark.integration
def test_hvdrun_check_build():
    """† horovodrun --check-build prints capabilities without launching."""
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "--check-build"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "Available Frameworks" in res.stdout
    assert "[X] JAX / Flax" in res.stdout
    assert "Available Tensor Operations" in res.stdout


@pytest.mark.integration
def test_hvdrun_missing_np():
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "--", "python", "x.py"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert res.returncode == 2
    assert "num-proc" in res.stderr


@pytest.mark.integration
@pytest.mark.slow  # tier-1 budget (~75s, heaviest e2e): CI multiprocess-e2e runs it
def test_hvdrun_elastic_checkpoint_world_size_circle(tmp_path):
    """Elastic x orbax checkpoint across WORLD SIZES (VERDICT r3 #5): train
    at np=4, rank 2 crashes (its 2-slot host is blacklisted -> np=2), the
    relaunch restores params+adam moments+step from orbax; mid-run the
    discovery file gains a third host -> grow circle back to np=4 with
    another restore.  The worker trains full-batch (gradient averaging is
    world-size-invariant), so EVERY logged loss must match the
    uninterrupted single-process oracle — which only holds if the model
    and optimizer state round-trip exactly through every restart."""
    from horovod_tpu.runner.cluster import local_ip
    my_ip = local_ip()  # the launcher's own notion of "this machine"
    assert my_ip not in ("localhost", "127.0.0.1"), my_ip
    hostsfile = tmp_path / "hosts.txt"
    hostsfile.write_text("localhost:2\n127.0.0.1:2\n")
    discover = tmp_path / "discover.sh"
    discover.write_text(f"#!/bin/sh\ncat {hostsfile}\n")
    discover.chmod(0o755)
    state = tmp_path / "state.json"
    log = tmp_path / "train.log"
    ckpt = tmp_path / "ckpts"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({"HVDTPU_TEST_STATE": str(state), "HVDTPU_TEST_LOG": str(log),
                "HVDTPU_TEST_CKPT": str(ckpt), "HVDTPU_TEST_KILL": "1",
                "HVDTPU_TEST_TOTAL": "24", "HVDTPU_TEST_STEP_DELAY": "0.3"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "4",
         "--min-np", "2", "--max-np", "4",
         "--host-discovery-script", str(discover), "--",
         sys.executable,
         os.path.join(REPO, "tests", "mp_elastic_ckpt_worker.py")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    try:
        # After the shrink incarnation (np=2) commits a few steps, offer a
        # fresh host so the growth watcher fires.
        deadline = time.time() + 180
        grown = False
        while time.time() < deadline and not grown:
            if log.exists():
                lines = log.read_text().splitlines()
                if any(ln.startswith("STEP rank=0 size=2 step=6")
                       for ln in lines):
                    hostsfile.write_text(
                        f"localhost:2\n127.0.0.1:2\n{my_ip}:2\n")
                    grown = True
            time.sleep(0.5)
        # 600s: the grow/shrink circle spawns 4 workers with fresh jax
        # compiles each resize; on a 2-core rig running right after the
        # full unit stage, 300s was observed marginal (it passes in ~90s
        # standalone) — the generous bound still catches real hangs.
        out, _ = proc.communicate(timeout=600)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out
    lines = log.read_text().splitlines()
    assert "START rank=0 size=4 resume_step=0" in lines, lines
    assert "CRASH rank=2 step=4" in lines, lines
    # Shrink leg: np=2 restored from the step-4 orbax checkpoint.
    assert "START rank=0 size=2 resume_step=4" in lines, lines
    # Grow leg: back at np=4, restored from a later checkpoint.
    grow_starts = [ln for ln in lines if ln.startswith(
        "START rank=0 size=4 resume_step=") and
        int(ln.rsplit("=", 1)[1]) > 4]
    assert grow_starts, lines
    assert any(ln.startswith("DONE rank=0 size=4 step=24")
               for ln in lines), lines

    # Loss continuity: every logged loss equals the uninterrupted oracle.
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    rng = np.random.RandomState(7)
    X = jnp.asarray(rng.randn(32, 4), jnp.float32)
    y = jnp.asarray(rng.randn(32, 1), jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w1": jax.random.normal(k1, (4, 8)) * 0.5,
              "b1": jnp.zeros((8,)),
              "w2": jax.random.normal(k2, (8, 1)) * 0.5,
              "b2": jnp.zeros((1,))}

    def loss_fn(p):
        h = jnp.tanh(X @ p["w1"] + p["b1"])
        return jnp.mean(((h @ p["w2"] + p["b2"]) - y) ** 2)

    tx = optax.adam(5e-2)
    opt_state = tx.init(params)
    oracle = []
    for _ in range(24):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        oracle.append(float(loss))
    logged = {}
    for ln in lines:
        if ln.startswith("STEP rank=0 "):
            fields = dict(f.split("=") for f in ln.split()[1:])
            logged[int(fields["step"])] = float(fields["loss"])
    assert logged, lines
    for step, loss in sorted(logged.items()):
        assert abs(loss - oracle[step]) < 1e-5, (
            f"step {step}: logged {loss} vs oracle {oracle[step]} — "
            "state did not survive the restart")


def test_host_hash_stable_and_overridable(monkeypatch):
    from horovod_tpu.runner.hosts import host_hash
    a = host_hash()
    assert a == host_hash() and len(a) == 32
    monkeypatch.setenv("HOROVOD_HOSTNAME", "shared-fs-node")
    b = host_hash()
    assert b != a
    assert host_hash(salt="split") != b


@pytest.mark.integration
@pytest.mark.slow
def test_chaos_recovery_scenario_harness():
    """Acceptance (the chaos-recovery CI job, wrapped): the np=4
    elastic scenario — injected rank death + flaky KV + delayed
    negotiation, driver blacklists and relaunches, results stay
    correct, a flight-recorder bundle names the injected fault — plus
    the determinism scenario (same seed => identical fault sequence).
    The serving scenario runs separately in the CI job (it needs a
    fresh process for hvd.init at np=1); its logic is tier-1-covered
    in test_chaos.py.  slow-marked: several runner startups."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for scenario in ("elastic", "determinism"):
        res = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.chaos.run",
             "--scenario", scenario],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
        assert res.returncode == 0, res.stdout + res.stderr
        assert "CHAOS-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.slow
def test_router_failover_scenario_harness():
    """Acceptance (the router-failover CI job, wrapped): two serving
    replicas behind the front-door router, an injected serving_step
    death kills one mid-stream, and every in-flight request completes
    token-identical on the survivor while /healthz and the router
    health gauge flip.  slow-marked: two full serving-worker
    startups."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("HVDTPU_FAULTS", None)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.chaos.run",
         "--scenario", "router"],
        capture_output=True, text=True, timeout=480, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "CHAOS-ROUTER-OK" in res.stdout, res.stdout
    assert "CHAOS-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.slow
def test_autoscale_recovery_scenario_harness():
    """Acceptance (the autoscale-recovery CI job, wrapped): the np=4
    expert-parallel MoE job under the closed-loop autoscaler — an
    injected rank death shrinks it to np=2 (blacklist), an SLO burn
    load spike holds scale-up pressure, and the controller grows it
    back to np=4 when the cooldown lapses, with exact state continuity
    and every decision on the metric/flight-recorder record.
    slow-marked: three full runner rounds plus a real 12s blacklist
    cooldown (~60-90s wall)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("HVDTPU_FAULTS", None)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.chaos.run",
         "--scenario", "autoscale"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "CHAOS-AUTOSCALE-OK" in res.stdout, res.stdout
    assert "CHAOS-OK" in res.stdout, res.stdout


@pytest.mark.integration
@pytest.mark.slow
def test_disagg_recovery_scenario_harness():
    """Acceptance (the disagg-recovery CI job, wrapped): np=4 replica
    workers pool-tagged 2 prefill + 2 decode behind the DisaggRouter,
    an injected mig_export death kills a prefill replica mid-migration
    (K chunk published, manifest not), and every request completes
    token-identical via durable-point replay on the pool sibling while
    the decode pool's eligibility gauge never dips.  slow-marked: four
    full serving-worker startups."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("HVDTPU_FAULTS", None)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.chaos.run",
         "--scenario", "disagg"],
        capture_output=True, text=True, timeout=480, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "CHAOS-DISAGG-OK" in res.stdout, res.stdout
    assert "CHAOS-OK" in res.stdout, res.stdout
