"""Paper Sec. 4 phase 2: fused Pallas selection kernel vs the while_loop.

The batched sampler amortizes beautifully at batch >= 32, but batch-1
latency — the serve KV-compaction path — is bounded by the phase-2
``lax.while_loop`` of O(k_eff) small steps (row totals and their prefix
-> scan of the picked row -> row product -> CGS2 -> colspace matvec ->
norms downdate, ``kernels.phase2_select.select_step``). The fused kernel
runs the same steps inside one ``pallas_call`` with the Gram-Schmidt
basis and residual norms resident in VMEM.

On CPU the fused path necessarily runs in *interpret mode* — the Pallas
grid (one sample per step, each running its own while_loop) is emulated
as XLA, with the in-kernel vreg rolls and iota-mask gathers where the
reference slices and indexes — so the CPU numbers below say nothing of
the compiled kernel on a TPU. Draw-for-draw equality of the two engines
is asserted before timing.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core import random_krondpp
# raw-engine benchmark: measures the sampling engine directly
# repro: ignore[facade-boundary]
from repro.sampling import SpectralCache
# repro: ignore[facade-boundary]
from repro.sampling.batched import sample_krondpp_batched
from .common import json_report, rescale_expected_size, timed, write_report

SIZES = (32, 32)          # N = 1024, the m=2 O(N^{3/2}) regime
TARGET_E = 12.0
BATCHES = (1, 8, 32)
REPEATS = {1: 50, 8: 10, 32: 4}
TRIALS = 5                # interleaved A/B trials; best-of to shed drift


def report_config() -> dict:
    """Fingerprinted workload parameters (see common.report_meta)."""
    return {"sizes": list(SIZES), "E_size": TARGET_E,
            "batches": list(BATCHES)}


def run(seed: int = 0) -> dict:
    dpp = rescale_expected_size(
        random_krondpp(jax.random.PRNGKey(seed), SIZES), TARGET_E)
    cache = SpectralCache()
    spec = cache.spectrum(dpp)
    k_max = spec.suggested_k_max()

    # correctness gate: identical picks on shared keys before timing
    key = jax.random.PRNGKey(seed + 1)
    p_ref, _, _ = sample_krondpp_batched(key, spec, k_max, 8,
                                         backend="reference")
    p_pal, _, _ = sample_krondpp_batched(key, spec, k_max, 8,
                                         backend="pallas")
    assert (np.asarray(p_ref) == np.asarray(p_pal)).all(), \
        "fused phase-2 diverged from the reference"

    rows = []
    for batch in BATCHES:
        key = jax.random.PRNGKey(seed + 10 + batch)
        reps = REPEATS[batch]

        def draw(backend):
            return sample_krondpp_batched(key, spec, k_max, batch,
                                          backend=backend)

        # interleaved best-of-TRIALS: each trial times both engines
        # back-to-back, so machine drift mid-benchmark cannot land
        # entirely on one side
        t_ref, t_pal = float("inf"), float("inf")
        for _ in range(TRIALS):
            t_ref = min(t_ref, timed(lambda: draw("reference"),
                                     repeats=reps)[0])
            t_pal = min(t_pal, timed(lambda: draw("pallas"),
                                     repeats=reps)[0])
        rows.append({
            "batch": batch,
            "while_loop_us": t_ref * 1e6,
            "fused_interpret_us": t_pal * 1e6,
            "fused_speedup": t_ref / t_pal,
        })
    return {"N": int(np.prod(SIZES)), "k_max": int(k_max),
            "E_size": TARGET_E,
            "backend": jax.default_backend(),
            "fused_mode": "compiled" if jax.default_backend() == "tpu"
            else "interpret",
            "draw_for_draw_identical": True,
            "rows": rows}


def main():
    res = run()
    for r in res["rows"]:
        print(f"phase2_fused,b{r['batch']},"
              f"{r['fused_interpret_us']:.0f},"
              f"{r['fused_speedup']:.2f}x vs while_loop "
              f"({r['while_loop_us']:.0f}us, {res['fused_mode']} mode)")
    json_report("paper_sec4_phase2_fused", res, config=report_config())
    write_report("paper_sec4_phase2_fused", res, config=report_config())


if __name__ == "__main__":
    main()
