"""Multi-process localhost distributed training — the TestDistBase
analog (test_dist_base.py:377 check_with_place: subprocesses on
127.0.0.1 free ports, trainer losses ≈ local losses)."""

import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(__file__)
RUNNER = os.path.join(HERE, "dist_mnist_runner.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_procs(nprocs, steps, timeout=240, mode="dp"):
    port = _free_port()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    repo_root = os.path.dirname(HERE)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, RUNNER, str(i), str(nprocs), str(port), str(steps),
             mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
        for i in range(nprocs)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"trainer failed:\n{err[-3000:]}"
        outs.append(out)
    return outs


def _losses(out):
    return {int(m.group(1)): float(m.group(2))
            for m in re.finditer(r"LOSS (\d+) ([\d.]+)", out)}


@pytest.fixture(scope="module")
def single_proc_losses():
    """The deterministic single-process baseline, computed once for
    every topology comparison in this module (5 steps covers all)."""
    return _losses(_run_procs(1, 5)[0])


@pytest.mark.slow
def test_two_process_dp_matches_single_process(single_proc_losses):
    steps = 5
    single = single_proc_losses
    multi = _run_procs(2, steps)
    l0, l1 = _losses(multi[0]), _losses(multi[1])
    assert len(single) == steps and len(l0) == steps
    for s in range(steps):
        # both workers report the same (psum'd) loss
        assert abs(l0[s] - l1[s]) < 1e-5
        # and it matches the single-process run on the same global batch
        assert abs(l0[s] - single[s]) < 1e-3, (
            f"step {s}: dist {l0[s]} vs local {single[s]}")


@pytest.mark.slow
def test_two_process_dp_fsdp_mesh_matches_single_process(single_proc_losses):
    """2 processes × 2 local virtual devices, mesh {dp: 2, fsdp: 2}:
    the data axis rides the cross-process (DCN analog) dimension while
    params/optimizer state shard over each process's local devices —
    the reference's multi-node NCCL2 topology plus pserver param
    slicing, as one mesh. Losses must match the plain single-process
    run on the same global batches."""
    steps = 4
    single = single_proc_losses  # 5-step baseline covers our 4
    multi = _run_procs(2, steps, mode="dp_fsdp")
    l0, l1 = _losses(multi[0]), _losses(multi[1])
    assert len(single) >= steps and len(l0) == steps
    for s in range(steps):
        assert abs(l0[s] - l1[s]) < 1e-5
        assert abs(l0[s] - single[s]) < 1e-3, (
            f"step {s}: dp×fsdp {l0[s]} vs local {single[s]}")


@pytest.mark.slow
def test_two_process_hoisted_accum_matches_single_process():
    """Cross-PROCESS hoisted accumulation: 2 processes × 1 device each,
    mesh {dp: 2}, DistStrategy(accum_steps=2, accum_exchange="hoisted")
    — each process scans its microbatches collective-free and the ONE
    pmean per optimizer step crosses the process (DCN analog) boundary.
    Per-step losses must match a single process holding the
    same global mesh on 2 local devices."""
    steps = 4
    single = _losses(_run_procs(1, steps, mode="dp_hoisted")[0])
    multi = _run_procs(2, steps, mode="dp_hoisted")
    l0, l1 = _losses(multi[0]), _losses(multi[1])
    assert len(single) == steps and len(l0) == steps
    for s in range(steps):
        assert abs(l0[s] - l1[s]) < 1e-5
        assert abs(l0[s] - single[s]) < 1e-3, (
            f"step {s}: hoisted 2-proc {l0[s]} vs same-mesh 1-proc "
            f"{single[s]}")


@pytest.mark.slow
def test_two_process_ring_sp_matches_single_process():
    """Cross-PROCESS ring attention: 2 processes x 4 devices, one
    {"sp": 8} axis, so the zigzag ring's permute hops cross the process
    (DCN-analog) boundary — the long-context multi-host shape. Per-step
    losses must match dense single-device training."""
    sp_runner = os.path.join(HERE, "dist_sp_runner.py")

    def run(nprocs, steps=3, timeout=420):
        port = _free_port()
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["PYTHONPATH"] = (os.path.dirname(HERE) + os.pathsep
                             + env.get("PYTHONPATH", ""))
        procs = [subprocess.Popen(
            [sys.executable, sp_runner, str(i), str(nprocs), str(port),
             str(steps)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True) for i in range(nprocs)]
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"sp trainer failed:\n{err[-3000:]}"
            outs.append(out)
        return outs

    ref = _losses(run(1)[0])
    outs = run(2)
    for out in outs:
        got = _losses(out)
        assert got.keys() == ref.keys()
        for s in ref:
            np.testing.assert_allclose(got[s], ref[s], rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_two_process_moe_ep_matches_single_process():
    """Cross-PROCESS expert parallelism: 2 processes x 4 devices, one
    {"ep": 8} axis — half the experts per process, the MoE dispatch
    all-to-all hops the process (DCN-analog) boundary. Per-step losses
    must match dense single-device training (aux off, ample capacity)."""
    ep_runner = os.path.join(HERE, "dist_ep_runner.py")

    def run(nprocs, steps=3, timeout=420):
        port = _free_port()
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["PYTHONPATH"] = (os.path.dirname(HERE) + os.pathsep
                             + env.get("PYTHONPATH", ""))
        procs = [subprocess.Popen(
            [sys.executable, ep_runner, str(i), str(nprocs), str(port),
             str(steps)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True) for i in range(nprocs)]
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"ep trainer failed:\n{err[-3000:]}"
            outs.append(out)
        return outs

    ref = _losses(run(1)[0])
    outs = run(2)
    for out in outs:
        got = _losses(out)
        assert got.keys() == ref.keys()
        for s in ref:
            np.testing.assert_allclose(got[s], ref[s], rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_two_process_pipeline_matches_single_process():
    """Cross-PROCESS pipeline parallelism: {"pp": 2, "dp": 2} with the
    pp axis laid across 2 processes, so stage-boundary activations hop
    the process (DCN-analog) link every microbatch. Per-step losses
    must match single-device training."""
    ref = _losses(_run_pp(1)[0])
    outs = _run_pp(2)
    for out in outs:
        got = _losses(out)
        assert got.keys() == ref.keys()
        for s in ref:
            np.testing.assert_allclose(got[s], ref[s], rtol=3e-4, atol=3e-4)


def _run_pp(nprocs, steps=3, timeout=420, extra=()):
    pp_runner = os.path.join(HERE, "dist_pp_runner.py")
    port = _free_port()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = (os.path.dirname(HERE) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, pp_runner, str(i), str(nprocs), str(port),
         str(steps), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True) for i in range(nprocs)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"pp trainer failed:\n{err[-3000:]}"
        outs.append(out)
    return outs


@pytest.mark.slow
def test_two_process_pipeline_dropout_matches_single_process():
    """Pipeline dropout across PROCESS boundaries: rng folds per
    (layer, microbatch, data-shard), all derived from mesh position —
    so a 2-process {"pp": 2, "dp": 2} run must draw the exact same
    masks as a 1-process run over the SAME global mesh (samemesh mode),
    giving per-step loss parity with dropout > 0 (round-4 verdict #5)."""
    ref = _losses(_run_pp(1, extra=("0.2", "1"))[0])
    outs = _run_pp(2, extra=("0.2",))
    assert ref, "reference produced no losses"
    for out in outs:
        got = _losses(out)
        assert got.keys() == ref.keys()
        for s in ref:
            np.testing.assert_allclose(got[s], ref[s], rtol=3e-4, atol=3e-4)
