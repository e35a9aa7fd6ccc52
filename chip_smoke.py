"""Smoke run of the KronDPP main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the Mesh runtime only

One chip: every phase drives the public entry points — the ``repro.dpp``
facade and the async serving tier — at the paper's size (the N = 100 x 100
Kron model of its runtime table) and compares what comes out with a plain
reference:

  sample    ``model.sample`` (DPP, batch 256) and ``model.sample(k=8)``
            (k-DPP, batch 64) on the compiled fused phase-2 kernel (its
            ``kernels.*`` counter, and a Mosaic custom call in the
            compiled program — never interpret mode); under
            ``default_matmul_precision("highest")`` fused picks equal the
            ``while_loop`` engine's on shared keys, but for rare rows that
            the two split at a float32 rounding near-tie of the inverse
            CDF (``divergence_gaps``); mean |Y| within 4σ of E|Y|.
  serving   ``model.serving`` answers 36 submits from two tenants; rows
            equal the batching-invariant keyed draws.
  learning  KrK and stochastic KrK under Armijo on 1024 device-drawn
            subsets: the log-likelihood never falls, health is not
            failing, and the final LL matches a float64 numpy evaluation.
  map       ``model.map(10)`` on a (64, 64) Kron model (the compiled
            Pallas greedy update, shown as for sampling) equals
            ``core.sampling.greedy_map_kdpp``.
  lowrank   ``dpp.LowRank`` at N = 65536, r = 32: mean |Y| within 4σ.

Four chips (``--chips 4``): ``Mesh(axes={"data": 4})`` sampling equals
``Local()`` bit for bit on shared keys, and a 4-way KrK fit matches the
local fit within ``MESH_FIT_RTOL``.

Per-phase lines carry wall times (compilation included): smoke figures,
not benchmarks. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``,
printed only when JAX finds a TPU and every phase passed; anything else
exits non-zero without it. Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SIZES = (100, 100)          # the paper's runtime-table Kron model, N = 1e4
EXPECTED_SIZE = 10.0
SAMPLE_BATCH = 256
KDPP_K, KDPP_BATCH = 8, 64
FIT_SUBSETS, FIT_SWEEPS, FIT_MINIBATCH = 1024, 4, 256
MAP_SIZES, MAP_K = (64, 64), 10
LOWRANK_N, LOWRANK_R, LOWRANK_E = 65536, 32, 8.0
#: Allowed |LL(device) - LL(float64 numpy)| relative to |LL|: float32
#: subset Cholesky factors and the N-term log(1 + λ) fold.
LL_RTOL = 1e-4
#: Fused vs reference phase 2: at most this share of rows may disagree,
#: each at a CDF boundary within this share of the total mass (about
#: eight float32 ulps; a one-item miss at N = 1e4 is ~1e-4).
MAX_DIVERGENT_ROWS, TIE_RTOL = 0.01, 1e-6
#: 4-way vs local KrK: per-factor relative Frobenius distance and LL.
MESH_FIT_RTOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, t0: float, **facts) -> None:
    print(json.dumps({"phase": phase, "wall_s": time.perf_counter() - t0,
                      **facts}), flush=True)


def _rows(batch):
    idx, mask = np.asarray(batch.indices), np.asarray(batch.mask)
    return [sorted(int(i) for i in r[m]) for r, m in zip(idx, mask)]


def _check_size(name: str, sizes, spec) -> dict:
    """Mean |Y| of n draws within 4σ/√n of E|Y|."""
    e, sd = float(spec.expected_size()), float(spec.size_std())
    mean = float(np.mean(sizes))
    tol = 4.0 * sd / np.sqrt(len(sizes))
    check(abs(mean - e) <= tol,
          f"{name}: mean |Y| {mean} is not within 4σ ({tol}) of E|Y| {e}")
    return {"mean_size": mean, "expected_size": e, "tol_4sigma": tol}


def _kernel_counters(t, op: str) -> dict:
    return {e: int(t.counter_value(f"kernels.{op}.{e}"))
            for e in ("pallas", "reference")}


def _calls_mosaic_kernel(fn, *args) -> bool:
    """Whether the program XLA compiles for ``fn(*args)`` calls a
    compiled Pallas (Mosaic) kernel: in interpret mode the kernel body is
    inlined as plain XLA operations and no ``tpu_custom_call`` remains."""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def phase_sample(model, key) -> None:
    from repro import dpp, obs
    t0 = time.perf_counter()
    spec = model.spectrum()
    k_max = spec.suggested_k_max()
    k_dpp = jax.random.fold_in(key, 1)
    with obs.use(obs.InMemoryTracker()) as t:
        batch = model.sample(key, SAMPLE_BATCH)
        kbatch = model.sample(k_dpp, KDPP_BATCH, k=KDPP_K)
        rows, krows = _rows(batch), _rows(kbatch)
    engines = _kernel_counters(t, "phase2_select")
    check(engines["pallas"] >= 2 and engines["reference"] == 0,
          f"phase 2 did not run on the fused kernel: {engines}")
    check(_calls_mosaic_kernel(
              lambda k: dpp.functional.sample_krondpp_batched(
                  k, spec, k_max, SAMPLE_BATCH)[0], key),
          "the sampling program holds no compiled phase-2 kernel")
    check(all(len(set(r)) == len(r) for r in rows),
          "a DPP draw repeats an item")
    check(all(len(set(r)) == KDPP_K for r in krows),
          f"a k-DPP draw does not hold {KDPP_K} distinct items")
    facts = _check_size("sample", [len(r) for r in rows], spec)

    def picks(engine, precision):
        with jax.default_matmul_precision(precision):
            p = dpp.functional.sample_krondpp_batched(
                key, spec, k_max, SAMPLE_BATCH, backend=engine)[0]
            q = dpp.functional.sample_kdpp_batched(
                k_dpp, spec, KDPP_K, KDPP_BATCH, backend=engine)
        return np.asarray(p), np.asarray(q)

    fused, kfused = picks("pallas", "highest")
    ref, kref = picks("reference", "highest")
    gaps = {"dpp": divergence_gaps(key, spec, k_max, fused, ref, kdpp=False),
            "kdpp": divergence_gaps(k_dpp, spec, KDPP_K, kfused, kref,
                                    kdpp=True)}
    for name, g in gaps.items():
        n = len(fused if name == "dpp" else kfused)
        check(len(g) <= MAX_DIVERGENT_ROWS * n
              and all(v <= TIE_RTOL for v in g.values()),
              f"{name}: fused and reference picks differ beyond rounding "
              f"near-ties: {g}")
    fused_d, kfused_d = picks("pallas", "default")
    ref_d, kref_d = picks("reference", "default")
    report("sample", t0, k_max=k_max, engines=engines,
           truncated=int(batch.truncation_count()), **facts,
           divergent_rows_highest={k: {str(r): v for r, v in g.items()}
                                   for k, g in gaps.items()},
           rows_differing_default_precision={
               "dpp": int((fused_d != ref_d).any(1).sum()),
               "kdpp": int((kfused_d != kref_d).any(1).sum())})


def divergence_gaps(key, spec, k_max: int, fused, ref, kdpp: bool) -> dict:
    """{row: gap} for every row where the two phase-2 engines disagree.

    Replays the row on the reference step function (XLA) up to its first
    differing step and measures how far the uniform's target mass
    ``u · total`` lies from the fused engine's pick, in units of the
    total mass: 0 when the target falls inside the pick's CDF interval.
    Two float32 implementations of one inverse CDF may split a target
    that sits within rounding of a boundary; a wrong kernel lands
    anywhere (gap ~ U(0, 1)).
    """
    out = {}
    for b in np.nonzero((fused != ref).any(1))[0]:
        t_div = int(np.nonzero(fused[b] != ref[b])[0][0])
        i = int(fused[b][t_div])
        if i < 0:
            out[int(b)] = 1.0
            continue
        lo, hi, r, total = (float(v) for v in _replay_cdf(
            key, int(b), tuple(spec.lams), tuple(spec.vecs), t_div, i,
            rows=len(fused), k_max=k_max, kdpp=kdpp))
        out[int(b)] = max(lo - r, r - hi, 0.0) / total
    return out


@functools.partial(jax.jit, static_argnames=("rows", "k_max", "kdpp"))
def _replay_cdf(key, b, lams, vecs, t_div, i, *, rows: int, k_max: int,
                kdpp: bool):
    """(lo, hi, u·total, total) at step ``t_div`` of row ``b`` of a batch
    of ``rows`` draws from ``key``, replayed on the reference step
    function: the prefix-mass interval [lo, hi) of item ``i`` and the
    inverse-CDF target. One compiled program, no op-by-op dispatch."""
    from repro.kernels import phase2_select as p2
    from repro.sampling.batched import _phase1_one
    from repro.sampling.kdpp import _phase1_one_kdpp, kdpp_keep_table
    from repro.sampling.spectral import log_product_spectrum
    key = jax.random.split(key, rows)[b]
    if kdpp:
        P, k0 = kdpp_keep_table(log_product_spectrum(lams), k_max)
        us, Gs, _ = _phase1_one_kdpp(key, vecs, P, k0, k_max)
    else:
        us, Gs, _, _ = _phase1_one(key, lams, vecs, k_max)
    G1, Gr = p2.canonical_pair(Gs)
    N1, Nr = int(G1.shape[0]), int(Gr.shape[0])
    us, g1, gr = p2.pad_pair(us, G1, Gr)

    def step(t, carry):
        norms, bt = carry
        _, _, norms, bt = p2.select_step(t, us[t], g1, gr, norms, bt,
                                         N1=N1, Nr=Nr)
        return norms, bt

    bt0 = jnp.zeros((g1.shape[1],) * 2, jnp.float32)
    norms, _ = jax.lax.fori_loop(0, t_div, step,
                                 (p2.init_norms(g1, gr), bt0))
    total, csum = p2.prefix_mass(norms, N1, Nr)
    hi = csum[i // Nr, i % Nr]
    return hi - norms[i // Nr, i % Nr], hi, us[t_div] * total, total


def phase_serving(model, seed: int) -> None:
    from repro.serving import ServingConfig
    t0 = time.perf_counter()
    tenants = ("interactive", "batch")
    rng = np.random.default_rng(seed)
    svc = model.serving(ServingConfig(max_batch=64, deadline_ms=5.0),
                        tenants={"interactive": 2, "batch": 1}, seed=seed)
    try:
        tickets = [svc.submit(int(rng.integers(1, 5)), tenant=tenants[i % 2])
                   for i in range(36)]
        got = [t.result(timeout=600.0) for t in tickets]
        flushes = svc.stats.flushes
    finally:
        svc.close()

    # row j of tenant T's request seq is drawn from
    # fold_in(fold_in(fold_in(seed, crc32(T)), seq), j), whatever the flush
    base = jax.random.PRNGKey(seed)
    keys = [jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        base, zlib.crc32(t.tenant.encode()) & 0x7FFFFFFF), t.seq), j)
        for t in tickets for j in range(t.num_samples)]
    want, _, _ = model.service(seed=seed).draw_keyed(jax.numpy.stack(keys))
    flat = [sorted(r) for rows in got for r in rows]
    check(flat == [sorted(r) for r in want],
          "served rows differ from the batching-invariant keyed draws")
    report("serving", t0, requests=len(tickets), rows=len(flat),
           flushes=flushes)


def numpy_log_likelihood(factors, batch) -> float:
    """Mean log P(Y) = logdet(L_Y) - logdet(L + I) in float64 numpy."""
    L1, L2 = (np.asarray(f, np.float64) for f in factors)
    n2 = L2.shape[0]
    log_z = np.log1p(np.outer(np.linalg.eigvalsh(L1),
                              np.linalg.eigvalsh(L2))).sum()
    total = 0.0
    for y in _rows(batch):
        y = np.asarray(y)
        r, u = y // n2, y % n2
        total += np.linalg.slogdet(L1[np.ix_(r, r)] * L2[np.ix_(u, u)])[1]
    return total / len(batch.indices) - log_z


def _nonempty(batch):
    from repro.core import SubsetBatch
    keep = np.asarray(batch.mask.any(axis=1))
    return SubsetBatch(batch.indices[keep], batch.mask[keep])


def phase_learning(model, seed: int, sizes=SIZES) -> None:
    from repro import dpp, obs
    t0 = time.perf_counter()
    data = _nonempty(model.sample(jax.random.PRNGKey(seed + 3), FIT_SUBSETS))
    init = dpp.random_kron(jax.random.PRNGKey(seed + 4), sizes)
    facts = {"subsets": int(data.indices.shape[0])}
    for algo, kw in (("krk", {}),
                     ("krk-stochastic", {"minibatch_size": FIT_MINIBATCH})):
        with obs.use(obs.InMemoryTracker()):
            rep = init.fit(data, algorithm=algo, iters=FIT_SWEEPS,
                           schedule=dpp.schedules.armijo(), **kw)
        lls = [float(v) for v in rep.log_likelihoods]
        check(all(b >= a - 1e-6 * abs(a) for a, b in zip(lls, lls[1:])),
              f"{algo}: log-likelihood fell: {lls}")
        check(rep.health["verdict"] != "failing",
              f"{algo}: health {rep.health}")
        ll64 = numpy_log_likelihood(rep.model.factors, data)
        check(abs(lls[-1] - ll64) <= LL_RTOL * abs(ll64),
              f"{algo}: LL {lls[-1]} vs float64 {ll64}")
        facts[algo] = {"lls": lls, "ll_float64": ll64,
                       "health": rep.health["verdict"]}
    report("learning", t0, **facts)


def phase_map(seed: int, sizes=MAP_SIZES, k: int = MAP_K) -> None:
    from repro import dpp, obs
    from repro.core.sampling import greedy_map_kdpp
    from repro.kernels import ops as kernel_ops
    t0 = time.perf_counter()
    model = dpp.random_kron(jax.random.PRNGKey(seed + 5), sizes)
    with obs.use(obs.InMemoryTracker()) as t:
        picks = np.asarray(model.map(k))
    engines = _kernel_counters(t, "greedy_map_update")
    check(engines["pallas"] >= 1 and engines["reference"] == 0,
          f"greedy MAP did not run on the Pallas kernel: {engines}")
    check(_calls_mosaic_kernel(lambda L: kernel_ops.greedy_map_kdpp(L, k),
                               model.dense_kernel()),
          "the greedy-MAP program holds no compiled kernel")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(greedy_map_kdpp(model.dense_kernel(), k))
    check(np.array_equal(np.sort(picks), np.sort(want)),
          f"map picks {picks.tolist()} != reference {want.tolist()}")
    report("map", t0, engines=engines, picks=picks.tolist(),
           same_order=bool(np.array_equal(picks, want)))


def phase_lowrank(seed: int, n: int = LOWRANK_N, r: int = LOWRANK_R) -> None:
    from repro import dpp
    t0 = time.perf_counter()
    V = jax.random.normal(jax.random.PRNGKey(n), (n, r)) * 0.7
    q = jnp.abs(jax.random.normal(jax.random.PRNGKey(n + 1), (n,))) + 0.3
    model = dpp.LowRank(V, q).rescale(LOWRANK_E)
    rows = _rows(model.sample(jax.random.PRNGKey(seed + 6), SAMPLE_BATCH))
    check(all(len(set(y)) == len(y) for y in rows),
          "a low-rank draw repeats an item")
    report("lowrank", t0, N=n, rank=r,
           **_check_size("lowrank", [len(y) for y in rows],
                         model.spectrum()))


def phase_mesh_sample(model, key) -> None:
    from repro import dpp
    t0 = time.perf_counter()
    local = model.sample(key, SAMPLE_BATCH, runtime=dpp.Local())
    mesh = model.sample(key, SAMPLE_BATCH, runtime=dpp.Mesh(axes={"data": 4}))
    check(np.array_equal(np.asarray(local.indices), np.asarray(mesh.indices))
          and np.array_equal(np.asarray(local.mask), np.asarray(mesh.mask)),
          "Mesh draws differ from Local draws on shared keys")
    report("mesh_sample", t0, rows=SAMPLE_BATCH, bit_equal=True)


def phase_mesh_fit(model, seed: int, sizes=SIZES) -> None:
    from repro import dpp
    t0 = time.perf_counter()
    rt = dpp.Mesh(axes={"data": 4})
    data = rt.even_batch(_nonempty(
        model.sample(jax.random.PRNGKey(seed + 3), FIT_SUBSETS)))
    init = dpp.random_kron(jax.random.PRNGKey(seed + 4), sizes)
    kw = dict(algorithm="krk", iters=FIT_SWEEPS,
              schedule=dpp.schedules.armijo())
    local = init.fit(data, **kw)
    mesh = init.fit(data, runtime=rt, **kw)
    dist = [float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                  / np.linalg.norm(np.asarray(a)))
            for a, b in zip(local.model.factors, mesh.model.factors)]
    ll_l, ll_m = local.log_likelihoods[-1], mesh.log_likelihoods[-1]
    check(max(dist) <= MESH_FIT_RTOL
          and abs(ll_l - ll_m) <= MESH_FIT_RTOL * abs(ll_l),
          f"4-way fit differs from local: factors {dist}, LL {ll_l} "
          f"vs {ll_m}")
    report("mesh_fit", t0, factor_rel_dist=dist, ll_local=ll_l,
           ll_mesh=ll_m, rtol=MESH_FIT_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole main path; 4: Mesh vs Local only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.configure()
    from repro import dpp

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(args.seed)
    model = dpp.random_kron(key, SIZES).rescale(EXPECTED_SIZE)
    sample_key = jax.random.fold_in(key, 7)
    if args.chips == 4:
        phase_mesh_sample(model, sample_key)
        phase_mesh_fit(model, args.seed)
    else:
        phase_sample(model, sample_key)
        phase_serving(model, args.seed)
        phase_learning(model, args.seed)
        phase_map(args.seed)
        phase_lowrank(args.seed)
    report("total", t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
