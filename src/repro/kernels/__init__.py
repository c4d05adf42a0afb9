"""Pallas TPU kernels (+ jnp oracles) for the perf-critical paths:

* flash_attention — train/prefill attention (streaming softmax);
* paged_attention — decode against the paged KV pool (scalar-prefetch
  page-table indirection);
* ssd_scan — Mamba-2 chunked scan (MXU intra-chunk + VMEM state carry);
* gc_compact — run-coalesced live-page copy (the paper's adaptive
  readahead adapted to HBM, DESIGN.md §2).

Kernels are validated in interpret mode on CPU against ``ref.py`` and
compiled ahead of time for a described TPU v5e in the tests;
``ops.*(use_pallas=True)`` runs them compiled on a TPU.
"""

from . import ops, ref

__all__ = ["ops", "ref"]
