"""Ray-Client equivalent (reference: python/ray/util/client — remote
drivers over one proxy connection, no shm/cluster access needed)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_remote_driver_subprocess(ray_start_regular):
    from ray_tpu.util.client import serve_client

    host, port = serve_client(0)

    script = textwrap.dedent(f"""
        import ray_tpu

        # Decorated BEFORE init (module-top pattern): must still route
        # through the client at call time.
        @ray_tpu.remote
        def early(x):
            return x * 3

        ray_tpu.init(address="ray://{host}:{port}")
        assert ray_tpu.get(early.remote(7)) == 21

        # Tasks
        @ray_tpu.remote
        def add(a, b):
            return a + b

        r1 = add.remote(2, 3)
        assert ray_tpu.get(r1) == 5

        # Refs as args (server-side pass-through, no client download)
        r2 = add.remote(r1, 10)
        assert ray_tpu.get(r2) == 15

        # put / get
        big = ray_tpu.put(list(range(1000)))
        assert ray_tpu.get(big)[-1] == 999

        # wait
        ready, rest = ray_tpu.wait([r1, r2], num_returns=2, timeout=30)
        assert len(ready) == 2 and not rest

        # Actors
        @ray_tpu.remote
        class Counter:
            def __init__(self, start):
                self.v = start

            def inc(self, k):
                self.v += k
                return self.v

        c = Counter.remote(100)
        assert ray_tpu.get(c.inc.remote(5)) == 105
        assert ray_tpu.get(c.inc.remote(5)) == 110
        ray_tpu.kill(c)

        # Errors surface client-side
        @ray_tpu.remote
        def boom():
            raise ValueError("kapow")

        try:
            ray_tpu.get(boom.remote())
        except Exception as e:
            assert "kapow" in str(e)
        else:
            raise AssertionError("error did not propagate")

        ray_tpu.shutdown()
        print("CLIENT-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # The client process must work WITHOUT joining the cluster: no store
    # path, no GCS bootstrap — only the proxy address.
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "CLIENT-OK" in out.stdout
