"""Hardware-trait predicates shared by kernel-strategy choices.

Kernels with a formulation choice (scatter vs gather, direct table vs sort)
ask these predicates instead of re-encoding backend names at every call
site — the strategy stays consistent across the engine and a new backend is
reasoned about once.
"""

from __future__ import annotations

import jax


def scatters_cheap() -> bool:
    """Whether a scatter is the cheaper way to build a front-compaction
    permutation: on XLA:CPU a 1 Mi-row scatter costs ~50 ms against ~8 ms a
    gather, and a searchsorted ~log2(n) gather sweeps. On a TPU v5e a sorted
    1:1 scatter is cheap too where it was timed (230 k positions into 2 Mi
    slots 1.9 ms, 1.5 M into 2 Mi 10.5 ms, 58 k 0.55 ms; PERF.md section 6,
    PR 27 and PR 30): the join's direct-address table no longer asks this
    question. ops/filtering.py's compaction still does; its scatter of
    unsorted destinations has not been timed on the chip."""
    return jax.default_backend() != "tpu"
