"""Local vs Mesh runtime scaling — samples/s and sweeps/s over one seam.

Sweeps the ``repro.dpp.runtime`` placements against each other on the
same model and keys: batched exact sampling (``model.sample``) and KrK
learning (``model.fit``, constant schedule + sharded stochastic
minibatches) under ``Local()`` vs ``Mesh(axes={"data": P})``, P = every
device JAX finds in this process.

It runs in this one process and never starts a child: a process that has
touched JAX holds the chip, so a child could not reach it. With a single
device it measures ``Local()`` only and says so (``"mesh": "not measured:
one device"``, no ``mesh_*`` fields). To measure the mesh on a CPU, start
it under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the
committed CPU report was made that way. Reading such CPU numbers
honestly: 8 forced host devices share one CPU's cores, so the ratios
bound the *sharding overhead* (shard_map launch + psum on shared memory)
plus whatever per-device thread parallelism XLA's CPU client grants; the
equivalence tests (tests/test_runtime.py) pin that the math is
placement-invariant.
"""

from __future__ import annotations


def run() -> dict:
    import jax
    import numpy as np

    from repro import dpp

    from .common import timed

    n_dev = jax.device_count()
    rt = dpp.Mesh(axes={"data": n_dev}) if n_dev > 1 else None
    model = dpp.random_kron(jax.random.PRNGKey(0), (16, 16)).rescale(12.0)
    rows = []

    # -- sampling: samples/s at two batch sizes -----------------------------
    for batch in (256, 1024):
        t_loc, _ = timed(lambda b=batch: model.sample(
            jax.random.PRNGKey(1), b), repeats=3)
        row = {"workload": f"sample_batch{batch}",
               "local_per_sec": round(batch / t_loc, 1)}
        if rt is not None:
            t_msh, _ = timed(lambda b=batch: model.sample(
                jax.random.PRNGKey(1), b, runtime=rt), repeats=3)
            row.update(mesh_per_sec=round(batch / t_msh, 1),
                       mesh_over_local=round(t_loc / t_msh, 3))
        rows.append(row)

    # -- learning: sweeps/s, full-batch krk and sharded stochastic ----------
    data = model.sample(jax.random.PRNGKey(2), 256)
    init = dpp.random_kron(jax.random.PRNGKey(3), (16, 16))
    for algo, kw in (("krk", {}),
                     ("krk-stochastic", {"minibatch_size": 8 * n_dev})):
        rep_l = init.fit(data, algorithm=algo, iters=6, a=0.7,
                         ll_mode="none", log_every=6, **kw)
        row = {"workload": f"fit_{algo}_n256",
               "local_per_sec": round(rep_l.sweeps_per_sec, 2)}
        if rt is not None:
            rep_m = init.fit(data, algorithm=algo, iters=6, a=0.7,
                             ll_mode="none", log_every=6, runtime=rt, **kw)
            row.update(mesh_per_sec=round(rep_m.sweeps_per_sec, 2),
                       mesh_over_local=round(
                           rep_m.sweeps_per_sec / rep_l.sweeps_per_sec, 3))
            if algo == "krk":   # placement must not move the answer
                assert np.allclose(np.asarray(rep_m.model.factors[0]),
                                   np.asarray(rep_l.model.factors[0]),
                                   rtol=1e-4, atol=1e-4)
        rows.append(row)

    dev = jax.devices()[0]
    out = {"devices": n_dev, "platform": dev.platform,
           "device_kind": dev.device_kind, "rows": rows}
    if rt is None:
        out["mesh"] = "not measured: one device"
    return out


def report_config() -> dict:
    """Fingerprinted workload parameters (see common.report_meta)."""
    return {"sizes": [16, 16], "E_size": 12.0, "batches": [256, 1024],
            "fit_n": 256, "fit_iters": 6}


def main() -> None:
    from .common import json_report, write_report
    res = run()
    json_report("runtime_scaling", res, config=report_config())
    write_report("runtime_scaling", res, config=report_config())
    for row in res["rows"]:
        print(f"runtime_scaling/{row['workload']},{row['local_per_sec']},"
              f"mesh {row.get('mesh_per_sec', 'not measured')}")


if __name__ == "__main__":
    main()
