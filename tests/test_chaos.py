"""Chaos-injection tests: per-handler rpc delays + kill-based chaos.

Reference test model: asio chaos (RAY_testing_asio_delay_us,
src/ray/common/asio/asio_chaos.h) delays named event-loop handlers to
amplify races; ResourceKiller-style node kills exercise recovery.
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu


def test_rpc_delay_injection_slows_named_handler():
    """RAY_TPU_TESTING_RPC_DELAY=handler=us injects latency into exactly
    that handler (driven in a subprocess so the env latches fresh)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = textwrap.dedent("""
        import os, sys, time
        os.environ["RAY_TPU_TESTING_RPC_DELAY"] = "kv_get=200000"
        sys.path.insert(0, %r)
        import ray_tpu
        from ray_tpu._private.worker import global_worker

        ray_tpu.init(num_cpus=1)
        w = global_worker()
        w.gcs_call("kv_put", {"ns": b"t", "key": b"k", "value": b"v"})

        t0 = time.perf_counter()
        w.gcs_call("kv_get", {"ns": b"t", "key": b"k"})
        slow = time.perf_counter() - t0

        t0 = time.perf_counter()
        w.gcs_call("kv_exists", {"ns": b"t", "key": b"k"})
        fast = time.perf_counter() - t0

        assert slow >= 0.18, f"delay not injected: {slow}"
        assert fast < 0.1, f"undelayed handler slowed: {fast}"
        ray_tpu.shutdown()
        print("CHAOS-OK")
    """) % (repo_root,)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "CHAOS-OK" in proc.stdout


def test_node_killer_recovery(ray_start_cluster):
    """Repeatedly killing a worker node's raylet mid-run must not lose
    retryable tasks (ResourceKiller pattern)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = ray_start_cluster()
    cluster.add_node(resources={"CPU": 2})
    ray_tpu.init(address=cluster.address)
    victim = cluster.add_node(resources={"CPU": 2, "spot": 2})
    cluster.wait_for_nodes(2)

    @ray_tpu.remote(max_retries=5, resources={"spot": 0.1})
    def chunk(i):
        time.sleep(0.1)
        return i

    refs = [chunk.remote(i) for i in range(12)]
    time.sleep(0.3)
    cluster.remove_node(victim)  # chaos: node dies mid-run
    # Replacement capacity arrives (autoscaler analog).
    cluster.add_node(resources={"CPU": 2, "spot": 2})
    out = ray_tpu.get(refs, timeout=60)
    assert sorted(out) == list(range(12))
