"""Fig. 4 analogue: strong scaling of the distributed TR across device
counts.  Each mesh shape reports the compile/steady split and its HBM
watermark, so the scaling rows carry the same record fields as every other
module.

On a TPU every mesh shape runs in this process over ``jax.devices()[:nd]``:
the chips belong to the process that first touched JAX, so a child could
not reach them.  On the CPU backend the host device count is fixed when JAX
starts, so each shape runs in a fresh child with that many host devices.
Either way a failed shape fails the run — no row stands in for it.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time


def measure(shape, n: int):
    """``(steady_us, compile_us, peak_hbm_bytes, hbm_source)`` of the fused
    distributed TR on a ``shape`` mesh over the first ``∏shape`` devices."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.semiring import minplus_orient_semiring as SR
    from repro.core.summa import dist_transitive_reduction, distribute_ell
    from repro.launch.mesh import make_test_mesh
    from repro.obs import watermark

    mesh = make_test_mesh(shape)
    rng = np.random.default_rng(0)
    deg = 8
    e = n * deg
    rows = rng.integers(0, n, e)
    cols = rng.integers(0, n, e)
    combos = rng.integers(0, 4, e)
    suf = rng.integers(1, 500, e).astype(np.float32)
    vals = np.full((e, 4), np.inf, np.float32)
    vals[np.arange(e), combos] = suf
    ok = rows != cols
    rd, _ = distribute_ell(jnp.asarray(rows), jnp.asarray(cols),
                           jnp.asarray(vals), jnp.asarray(ok), n_rows=n,
                           n_cols=n, block_capacity=3 * deg, semiring=SR,
                           mesh=mesh)
    with watermark() as wm:
        t0 = time.perf_counter()
        _, _, nnz = dist_transitive_reduction(rd, fuzz=100.0, fused=True)
        nnz.block_until_ready()
        compile_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        for _ in range(3):
            _, _, nnz = dist_transitive_reduction(rd, fuzz=100.0, fused=True)
            nnz.block_until_ready()
        steady_us = (time.perf_counter() - t0) / 3 * 1e6
    return steady_us, compile_us, wm.peak_hbm_bytes, wm.source


def _measure_in_child(shape, n: int):
    """:func:`measure` in a child with ``∏shape`` CPU host devices."""
    nd = shape[0] * shape[1]
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nd}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")]
    )
    code = (
        "from benchmarks.bench_scaling import measure\n"
        f"print(*measure({tuple(shape)!r}, {n}))\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560, check=True)
    us, compile_us, peak, source = r.stdout.strip().splitlines()[-1].split()
    return float(us), float(compile_us), int(peak), source


def run(shapes=((1, 1), (2, 1), (2, 2)), n=4096):
    """One row per mesh shape: steady-state wall-clock, parallel efficiency
    vs the first shape, compile time and HBM watermark."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    rows = []
    base = None
    for shape in shapes:
        nd = shape[0] * shape[1]
        if on_tpu:
            if nd > len(jax.devices()):
                raise RuntimeError(
                    f"mesh {shape} needs {nd} devices; "
                    f"{len(jax.devices())} present"
                )
            us, compile_us, peak, source = measure(shape, n)
        else:
            us, compile_us, peak, source = _measure_in_child(shape, n)
        if base is None:
            base = us
        rows.append((f"scaling/P{nd}", us,
                     f"efficiency={base / (us * nd):.2f}", compile_us,
                     peak, source))
    return rows
