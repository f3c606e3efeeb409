"""Multi-device tests (8 virtual CPU devices via a subprocess, since device
count locks at first jax init)."""

import os
import subprocess
import sys
import textwrap

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=_ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return p.stdout


def test_bottom_up_mesh8_gates():
    """Bottom-up on rmat-10 over an 8-device ``("data",)`` mesh: φ equals
    the oracle, every device takes part, lane padding stays under the
    0.674 waste the lane packer was held to before waste-aware packing,
    and the shape ladder keeps the run to at most 8 compiled programs."""
    out = _run("""
        import warnings
        import jax
        from repro.core.bottom_up import bottom_up_decompose
        from repro.core.serial import alg2_truss
        from tests.conftest import rmat_graph
        mesh = jax.make_mesh((8,), ("data",))
        n, ce = rmat_graph(scale=10, edge_factor=6, seed=7)
        warnings.simplefilter("ignore")
        res = bottom_up_decompose(n, ce, max(len(ce) // 32, 256), mesh=mesh)
        s = res.stats
        assert (res.phi == alg2_truss(n, ce)).all()
        assert s.devices == 8 and s.sharded_rounds > 0, s
        assert s.padding_waste < 0.674, s.padding_waste
        assert s.compiles <= 8, s.compiles
        print("MESH8-OK")
    """)
    assert "MESH8-OK" in out
