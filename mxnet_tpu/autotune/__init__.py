"""mxnet_tpu.autotune — telemetry-driven autotuning (ISSUE 9).

Closes the loop the cost registry opened (ROADMAP item 4): instead of
frozen hand-picked constants, hot-path tunables are **searched** over a
declared config space, measured on-device with warmup/repeat discipline,
and the winners persisted per (device kind, kernel, shape signature) —
"Learning to Optimize Tensor Programs" (PAPERS.md 1805.08166) with a
grid/greedy searcher standing in for the learned cost model.

Pieces:

* ``space``  — tuning-space declarations (params + constraints + the
  hand-tuned default); ships the ``dconv_col_pallas`` block-shape space
  under the existing VMEM guard.
* ``measure`` — fresh-jit-per-candidate timing (median of synced repeats
  after warmup), counted in ``autotune_trials_total``.
* ``search`` — exhaustive grid for small spaces, greedy coordinate
  descent beyond ``max_trials``; the default is measured first and wins
  ties (adopting a winner can never regress shipped behavior).
  ``predict_then_measure`` (ISSUE 18) ranks the grid with the learned
  cost model and measures only the default + top-k.
* ``costmodel`` — the learned cost model itself (ISSUE 18): pure-numpy
  ridge over the store's accumulated (config, shape sig, ledger
  features) → seconds rows; advisory — measurement stays the source of
  truth.
* ``store``  — the persistent winner store (``MXNET_AUTOTUNE_CACHE``)
  with compile_cache-style env-fingerprint invalidation: stale or corrupt
  entries are silent misses that re-search overwrites, never crashes.
* ``ladder`` — the serving bucket-ladder tuner: replays a recorded
  loadgen request trace and minimizes padding inflation x compile count.

Everything gates on ``MXNET_AUTOTUNE``: unset, the wired dispatch sites
(``ops/pallas_kernels._dconv_grid``, ``serving.Engine`` ladder selection)
never import this package and behave byte-identically to a build without
it.  ``tools/autotune.py`` is the search/show/clear CLI.
"""
from __future__ import annotations

from . import costmodel, ladder, measure, search, space, store
from .costmodel import CostModel, model_for, training_rows
from .ladder import LADDER_KERNEL, ladder_sig, objective, propose
from .measure import (failed_measurements, measure_candidate, measurements,
                      time_callable)
from .search import predict_then_measure
from .search import search as run_search
from .space import (TuningSpace, dconv_shape_sig, fused_step_sig, get_space,
                    nms_shape_sig, quant_shape_sig, register_space, spaces)
from .store import (clear, config_for, enabled, entries, lookup, override,
                    record, stats, store_path)

__all__ = [
    "costmodel", "ladder", "measure", "search", "space", "store",
    "CostModel", "model_for", "training_rows",
    "LADDER_KERNEL", "ladder_sig", "objective", "propose",
    "failed_measurements", "measure_candidate", "measurements",
    "time_callable", "predict_then_measure", "run_search",
    "TuningSpace", "dconv_shape_sig", "fused_step_sig", "get_space",
    "nms_shape_sig", "quant_shape_sig", "register_space", "spaces",
    "clear", "config_for", "enabled", "entries", "lookup", "override",
    "record", "stats", "store_path", "tuned_ladder",
]


def tuned_ladder(sample_shapes):
    """Persisted ladder rungs for one serving stream's declared per-sample
    shapes, or None — the Engine's construction-time lookup (only called
    under ``MXNET_AUTOTUNE``; a hit is a plain tuple ready for
    ``BucketLadder``)."""
    cfg = lookup(LADDER_KERNEL, ladder_sig(sample_shapes))
    if not cfg:
        return None
    sizes = cfg.get("batch_sizes")
    # list/tuple only: a malformed winner (e.g. the string "248", whose
    # characters would iterate into rungs (2, 4, 8)) keeps the default
    if not isinstance(sizes, (list, tuple)):
        return None
    try:
        sizes = tuple(int(b) for b in sizes)
    except (TypeError, ValueError):
        return None
    return sizes if sizes and min(sizes) >= 1 else None
