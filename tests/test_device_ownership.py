"""One process per accelerator: the runtime's worker processes never
initialise a JAX backend.

A chip belongs to the one process that first initialises a backend on it;
a forked pool worker or a cluster node agent that did so as well would fail
or hang.  The drivers start the runtime (which forks the process pool and
spawns the agents) before the parent's first device use, and task bodies
on those workers are numpy.  Each case runs in a fresh interpreter so that
no backend exists before the runtime starts, as in a driver; the same file
runs unchanged on a TPU host, where the parent then holds the chip.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import os
from repro.core import api

def backends_here():
    from jax._src import xla_bridge
    return os.getpid(), sorted(xla_bridge._backends)

rt = api.runtime_start(n_workers=2, backend={backend!r}, max_retries=0)
try:
    import jax
    import jax.numpy as jnp
    jnp.ones(8).block_until_ready()          # the parent takes the device
    probe = api.task(backends_here, name="probe")
    seen = api.wait_on([probe() for _ in range(4)])
finally:
    api.runtime_stop()
print("platform", jax.devices()[0].platform)
for pid, backends in seen:
    assert pid != os.getpid(), "probe ran in the parent"
    assert backends == [], (pid, backends)
print("OK", len({{pid for pid, _ in seen}}), "worker processes")
"""


@pytest.mark.parametrize("backend", ["process", "cluster"])
def test_workers_never_initialise_a_jax_backend(backend):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(PROBE.format(backend=backend))],
        capture_output=True, text=True, env=env, timeout=300)
    print(out.stdout)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout, out.stdout
