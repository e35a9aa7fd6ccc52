"""Readings that the k-DPP cell's limits are set from, with its faults.

    python3 bench/kdpp_control.py --workload kron-kdpp-k8-b64 \
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--fault esp_k_minus_1] \
        [--seconds 2]

``bench/control.py`` with the k-DPP program's faults in place of the
learning engine's; the readings and the JSON line of each are that
file's. ``--fault`` plants, for every seed:

    esp_k_minus_1   phase 1 reads the ESP table at k' - 1 where it wants
                    e_{k'} (every column of the table moved one to the
                    right, e_0 kept), so each inclusion probability is
                    that of a draw one item smaller
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` planted (see the module docstring)."""
    if fault is None:
        yield
        return
    if fault != "esp_k_minus_1":
        raise SystemExit(f"unknown fault {fault!r}")
    import jax.numpy as jnp
    from repro.sampling import kdpp
    orig = kdpp.log_esp_table

    def shifted(log_lam, k):
        t = orig(log_lam, k)
        return jnp.concatenate([t[:, :1], t[:, :-1]], axis=1)
    # the jitted draw is cached per shape: the fault has to reach a fresh
    # trace, and the sound program one after it
    kdpp._sample_kdpp_batched.clear_cache()
    kdpp.log_esp_table = shifted
    try:
        yield
    finally:
        kdpp.log_esp_table = orig
        kdpp._sample_kdpp_batched.clear_cache()


def main(argv=None) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import control
    with mock.patch.object(control, "planted", planted):
        return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
