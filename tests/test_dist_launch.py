"""Launch-based multi-process distributed tests.

Runs ``tools/launch.py --launcher local -n 2`` on the nightly
dist_sync_kvstore script — the reference's CI pattern
(``ci/docker/runtime_functions.sh:805-812`` launching
``tests/nightly/dist_sync_kvstore.py`` with ``--launcher local``) — so the
suite executes the true multi-process jax.distributed path (gloo collectives
across two OS processes), not just the in-process virtual-device mesh.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _launch(num_workers, script, extra_env=None, timeout=300):
    env = dict(os.environ)
    # each worker is its own single-CPU-device jax process; drop the
    # test mesh forcing
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "launch.py"), "-n", str(num_workers),
         "--", sys.executable, str(script)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=str(REPO))


@pytest.mark.slow
def test_dist_sync_kvstore_two_workers():
    out = _launch(2, REPO / "tests" / "nightly" / "dist_sync_kvstore.py")
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    for rank in (0, 1):
        assert ("rank %d: DIST_KVSTORE_OK" % rank) in out.stdout, out.stdout[-4000:]
        assert ("rank %d: DIST_TRAINER_OK" % rank) in out.stdout, out.stdout[-4000:]
        assert ("rank %d: DIST_HEARTBEAT_OK" % rank) in out.stdout, out.stdout[-4000:]
        assert ("rank %d: DIST_RING_ATTENTION_OK" % rank) in out.stdout, \
            out.stdout[-4000:]


def test_launch_cli_rejects_empty_command():
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "launch.py"), "-n", "2"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode != 0


@pytest.mark.slow
def test_dist_sync_kvstore_four_workers():
    """Scale the exact-value kvstore assertions past n=2 (the reference's
    nightly runs 7 workers, ci/docker/runtime_functions.sh:805-812)."""
    out = _launch(4, REPO / "tests" / "nightly" / "dist_sync_kvstore.py",
                  timeout=600)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    for rank in range(4):
        for marker in ("DIST_KVSTORE_OK", "DIST_TRAINER_OK",
                       "DIST_HEARTBEAT_OK", "DIST_RING_ATTENTION_OK"):
            assert ("rank %d: %s" % (rank, marker)) in out.stdout, \
                out.stdout[-4000:]


@pytest.mark.slow
def test_all_reduce_branches_multiprocess():
    """Every all_reduce code path (per-device and pre-reduce fallback,
    sum/mean/max/min) with exact values across 2 OS processes."""
    out = _launch(2, REPO / "tests" / "nightly" / "dist_allreduce_branches.py")
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    for rank in (0, 1):
        for marker in ("BRANCH_PER_DEVICE_SUM_OK", "BRANCH_PER_DEVICE_MEAN_OK",
                       "BRANCH_PER_DEVICE_MAXMIN_OK",
                       "BRANCH_PREREDUCE_SUM_OK", "BRANCH_PREREDUCE_MEAN_OK",
                       "BRANCH_PREREDUCE_MAX_OK", "BRANCH_PREREDUCE_MIN_OK"):
            assert ("rank %d: %s" % (rank, marker)) in out.stdout, \
                out.stdout[-4000:]


@pytest.mark.slow
def test_worker_kill_detection_and_elastic_resume():
    """Rank 2 dies hard mid-job; survivors must observe it via
    get_dead_nodes and run_elastic must resume from the last committed
    checkpoint (reference GetDeadNodes + is_recovery flow)."""
    out = _launch(3, REPO / "tests" / "nightly" / "dist_elastic_kill.py",
                  timeout=300)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    assert "rank 2: DYING_NOW" in out.stdout
    for rank in (0, 1):
        assert ("rank %d: DEAD_NODE_DETECTED" % rank) in out.stdout, \
            out.stdout[-4000:]
        assert ("rank %d: ELASTIC_RESUME_OK" % rank) in out.stdout, \
            out.stdout[-4000:]


@pytest.mark.slow
def test_dist_async_kvstore_two_workers():
    """Cross-process dist_async contract: aggregation works, the
    PS-requiring updater form fails loudly on every rank (reference
    tests/nightly/dist_async_kvstore.py counterpart)."""
    out = _launch(2, REPO / "tests" / "nightly" / "dist_async_kvstore.py")
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    for rank in (0, 1):
        assert ("rank %d: ASYNC_PUSHPULL_OK" % rank) in out.stdout
        assert ("rank %d: ASYNC_UPDATER_REJECTED_OK" % rank) in out.stdout
