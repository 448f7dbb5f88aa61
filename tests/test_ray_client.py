"""Ray-client proxy mode tests.

Reference test model: python/ray/util/client tests — a remote driver
process connects via ray:// and exercises put/get/tasks/actors against
the real cluster through the proxy.
"""

import subprocess
import sys
import textwrap

import pytest

import ray_tpu
from ray_tpu.util.client import ClientProxyServer


@pytest.fixture(scope="module")
def client_proxy(ray_start_regular):
    proxy = ClientProxyServer(port=0).start()
    yield proxy
    proxy.stop()


CLIENT_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import ray_tpu

    ray_tpu.init(address="ray://127.0.0.1:{port}")

    ref = ray_tpu.put({{"k": [1, 2, 3]}})
    assert ray_tpu.get(ref) == {{"k": [1, 2, 3]}}

    @ray_tpu.remote
    def double(x):
        return 2 * x

    refs = [double.remote(i) for i in range(5)]
    ready, pending = ray_tpu.wait(refs, num_returns=5, timeout=30)
    assert len(ready) == 5 and not pending
    assert ray_tpu.get(refs) == [0, 2, 4, 6, 8]

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0
        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    assert ray_tpu.get(c.incr.remote(5)) == 6
    ray_tpu.kill(c)
    ray_tpu.shutdown()
    print("CLIENT-OK")
""")


def test_client_end_to_end(client_proxy):
    script = CLIENT_SCRIPT.format(repo="/root/repo",
                                  port=client_proxy.port)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLIENT-OK" in proc.stdout


def test_client_objects_visible_to_cluster(client_proxy):
    """Objects put via the proxy are real cluster objects: the in-process
    driver can consume refs produced client-side (shared GCS/object
    plane)."""
    from ray_tpu.util.client.worker import ClientWorker

    cw = ClientWorker("127.0.0.1", client_proxy.port)
    try:
        ref = cw.put([7, 8])
        # The proxy pinned it; the local driver can get it directly.
        assert ray_tpu.get(ref, timeout=10) == [7, 8]
    finally:
        cw.disconnect()
