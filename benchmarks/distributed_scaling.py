"""Distributed Contour (paper §IV-G analogue): shard_map weak-scaling dry
measurement + the beyond-paper local-rounds trade.

Runs in a subprocess with 8 virtual CPU devices (the bench process
itself keeps the real device count), reporting global rounds and
collective bytes per convergence for local_rounds in {1, 2, 4} — the
§Perf hillclimb lever for the contour-cc production cells.  The child is
held to the CPU (``JAX_PLATFORMS=cpu``): it is a rehearsal of the mesh
logic, its times are CPU times, and a parent that already holds the
accelerator keeps it.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import time
    import numpy as np
    import jax
    from repro.connectivity.distributed import distributed_contour
    from repro.graphs import generators as gen
    from repro.graphs.oracle import connected_components_oracle

    from repro import jax_compat
    mesh = jax_compat.make_mesh((8,), ("data",))
    graphs = {
        "path_32k": gen.path(32768, seed=1),
        "grid_128": gen.grid2d(128, 128),
        "rmat_14": gen.rmat(14, seed=2),
    }
    print(f"CPU rehearsal on {len(jax.devices())} virtual "
          f"{jax.devices()[0].platform} devices; times are not chip times")
    print(f"{'graph':10s} {'lr':>3s} {'rounds':>7s} {'coll_MB/conv':>13s} "
          f"{'time_s':>8s}")
    for name, g in graphs.items():
        oracle = connected_components_oracle(*g.to_numpy())
        for lr in (1, 2, 4):
            t0 = time.perf_counter()
            labels, rounds, _, _ = distributed_contour(
                g, mesh, edge_axes=("data",), local_rounds=lr)
            dt = time.perf_counter() - t0
            ok = (np.asarray(labels) == oracle).all()
            assert ok, (name, lr)
            # per-round collective = one n x 4B label min-all-reduce
            mb = int(rounds) * g.n_vertices * 4 * 2 * 7 / 8 / 1e6
            print(f"{name:10s} {lr:3d} {int(rounds):7d} {mb:13.2f} "
                  f"{dt:8.2f}")
    print("DISTRIBUTED_BENCH_OK")
""")


def main(fast: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # one process per chip: the child never takes the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", BODY], capture_output=True,
                         text=True, env=env, timeout=900)
    print(out.stdout)
    if out.returncode != 0:
        print(out.stderr[-1500:])
        raise SystemExit("distributed bench failed")


if __name__ == "__main__":
    main()
