"""Pallas TPU kernels for Tesserae's compute hot spots.

Three kernels, each with a ``ref.py`` pure-jnp oracle and a jit'd wrapper in
``ops.py``; all run compiled on TPU and in interpret mode on the CPU
(where the tests validate them), written with explicit BlockSpec VMEM
tiling for TPU v5e as the target:

* ``lap_bid``        — the auction-algorithm bid step (masked row top-2 over
                       the benefit-minus-price matrix).  This is the inner
                       loop of the §4.1/§4.2 assignment solves.
* ``migration_cost`` — Algorithm 3 lines 2-7: the pairwise symmetric-
                       difference cost matrix over GPU job-sets, the O(k^2)
                       construction that dominates Algorithm 2 at large
                       cluster sizes.
* ``flash_attention``— causal flash attention for the workload substrate
                       (the perf-critical compute layer of the jobs
                       Tesserae schedules).
* ``flash_decode``   — flash-decoding: one query token against a long
                       (ring-buffer) KV cache, GQA-aware without
                       materialising repeated KV heads.  The decode_32k /
                       long_500k serving hot spot.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
