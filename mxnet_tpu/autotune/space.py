"""Tuning-space declarations (ISSUE 9).

A :class:`TuningSpace` is a kernel's statement of what is tunable: named
parameters with finite choice lists, the hand-tuned **default** config (the
shipped behavior, always config #0 — the searcher measures it first and a
candidate must beat it STRICTLY to replace it), and an optional constraint
predicate over (config, shape context) that prunes configs the hardware
would reject — the declared-space half of the "Learning to Optimize Tensor
Programs" loop (PAPERS.md 1805.08166), with the grid/greedy searcher in
``search.py`` standing in for the learned cost model.

Registered spaces (this module, at import):

* ``dconv_col_pallas`` — the block size ``nblk`` of the fused
  deformable-conv sampling kernel (`ops/pallas_kernels.py`): samples per
  grid step, which lie on the LANES of every block the kernels read and
  write (the per-sample rows, ``col^T (BG, C, N)`` and its cotangent:
  channels-major since PR 32).  Constrained by the same
  ``dconv_bwd_vmem_bytes`` VMEM guard that drives the pallas-vs-XLA auto
  branch: a candidate whose backward working set would hard-fail Mosaic is
  never measured.  A block under 128 lanes that is not all of N does not
  compile for the chip (before PR 32 neither: its lane slices are
  unaligned) and counts as a failed trial; the interpreter takes any.
* ``nms_alive_pallas`` — the box-tile size ``tile`` of the blocked greedy
  NMS kernel (lane-aligned multiples of 128; ``nms_fits_vmem`` prunes
  tiles whose per-image working set would blow VMEM at the problem's N).
* ``quantize_int8_pallas`` / ``dequantize_int8_pallas`` — the row-block
  ``block`` of the tiled elementwise int8 kernels (``quant_fits_vmem``).
* ``fused_step_layout`` — the one NON-kernel space (ISSUE 18): fused
  train-step layout knobs, ZeRO-1 on/off × input prefetch depth, measured
  end-to-end through ``FusedStepper`` on a tiny model by the CLI runner.
  The constraint prunes ``zero=1`` off-mesh (``MXNET_FUSED_ZERO`` is only
  consulted on the mesh path).  The winner is adopted by operators (set
  ``MXNET_FUSED_ZERO`` / ``PrefetchingIter(prefetch_depth=...)`` from the
  stored config), not by a trace-time dispatch site.
"""
from __future__ import annotations

import itertools

__all__ = ["TuningSpace", "register_space", "get_space", "spaces",
           "dconv_shape_sig", "nms_shape_sig", "quant_shape_sig",
           "fused_step_sig"]

_SPACES = {}


class TuningSpace:
    """Declared config space of one kernel.

    Parameters
    ----------
    name : str
        Kernel name — the store/lookup key component.
    params : dict
        ``param name -> sequence of choices`` (finite, order preserved).
    default : dict
        The hand-tuned config; must pick one choice per param.  Always
        admitted (it is the shipped behavior) even where the constraint
        would prune it.
    constraint : callable, optional
        ``constraint(config, **ctx) -> bool``; ``ctx`` is the shape
        context handed to :meth:`configs` (e.g. N/HW/C/itemsize for
        dconv).  False prunes the candidate.
    """

    def __init__(self, name, params, default, constraint=None):
        self.name = str(name)
        self.params = {str(k): tuple(v) for k, v in params.items()}
        for k, v in self.params.items():
            if not v:
                raise ValueError("empty choice list for %r.%s" % (name, k))
        self.default = dict(default)
        if set(self.default) != set(self.params):
            raise ValueError(
                "default config keys %s != params %s"
                % (sorted(self.default), sorted(self.params)))
        self.constraint = constraint

    def admits(self, config, **ctx):
        """Constraint check; the default config is always admitted."""
        if config == self.default:
            return True
        if self.constraint is None:
            return True
        return bool(self.constraint(config, **ctx))

    def iter_configs(self, **ctx):
        """Constraint-filtered grid as a lazy generator, DEFAULT FIRST
        (the searcher's never-worse guarantee hangs on measuring it).
        Lazy so the searcher can count just past ``max_trials`` to pick
        grid-vs-greedy without materializing a huge product."""
        names = sorted(self.params)
        yield dict(self.default)
        for combo in itertools.product(*(self.params[n] for n in names)):
            cfg = dict(zip(names, combo))
            if cfg != self.default and self.admits(cfg, **ctx):
                yield cfg

    def configs(self, **ctx):
        """Constraint-filtered full grid as a list (see iter_configs)."""
        return list(self.iter_configs(**ctx))

    def __repr__(self):
        return "TuningSpace(%s: %s)" % (
            self.name, ", ".join("%s in %s" % kv
                                 for kv in sorted(self.params.items())))


def register_space(space):
    """Register (or replace) a kernel's declared space."""
    _SPACES[space.name] = space
    return space


def get_space(name):
    sp = _SPACES.get(str(name))
    if sp is None:
        raise KeyError("no tuning space registered for %r (have: %s)"
                       % (name, sorted(_SPACES)))
    return sp


def spaces():
    """name -> TuningSpace for every registered kernel."""
    return dict(_SPACES)


# -- dconv_col_pallas ---------------------------------------------------------
def dconv_shape_sig(N, HW, C, itemsize):
    """Shape signature of one dconv_col_pallas problem — the store key
    component.  BG is excluded: the grid iterates it, so the per-step
    working set (what ``nblk`` trades against) does not depend on it."""
    return "N%d-HW%d-C%d-i%d" % (int(N), int(HW), int(C), int(itemsize))


def _dconv_constraint(config, N=None, HW=None, C=None, itemsize=4, **_):
    """A candidate block size must keep the BACKWARD working set (the
    larger pass) inside the same VMEM budget the auto branch enforces —
    ``pallas_kernels.dconv_fits_vmem`` with the candidate's EFFECTIVE
    ``nblk`` (the dispatch site caps at N, so admission must judge the
    block size that would actually run, not the uncapped declaration)."""
    from ..ops.pallas_kernels import dconv_fits_vmem

    if HW is None or C is None:
        return True
    nblk = int(config["nblk"])
    if N is not None:
        nblk = min(nblk, int(N))
    return dconv_fits_vmem(int(HW), int(C), int(itemsize), nblk=nblk)


register_space(TuningSpace(
    "dconv_col_pallas",
    # 128 is the shipped _DCONV_NBLK; 32 and 64 run in the interpreter and
    # as the whole of a small N only (module docstring)
    params={"nblk": (32, 64, 128, 256, 512)},
    default={"nblk": 128},
    constraint=_dconv_constraint))


# -- nms_alive_pallas ---------------------------------------------------------
def nms_shape_sig(B, N):
    """Shape signature of one blocked-NMS problem: images × boxes.  B is
    kept (unlike dconv's BG) because the whole per-image column block is
    VMEM-resident — batching changes nothing per grid step, but N drives
    both padding waste and the fixed-point tile cost."""
    return "B%d-N%d" % (int(B), int(N))


def _nms_constraint(config, N=None, **_):
    """Lane alignment (every in-kernel slice is over the 128-lane axis)
    plus the per-image VMEM working-set guard at the problem's N."""
    from ..ops.pallas_kernels import _LANE, nms_fits_vmem

    tile = int(config["tile"])
    if tile < _LANE or tile % _LANE:
        return False
    if N is None:
        return True
    return nms_fits_vmem(int(N), tile=tile)


register_space(TuningSpace(
    "nms_alive_pallas",
    params={"tile": (128, 256, 512, 1024)},
    default={"tile": 256},   # the shipped _NMS_TILE
    constraint=_nms_constraint))


# -- quantize/dequantize_int8_pallas ------------------------------------------
def quant_shape_sig(rows, itemsize):
    """Shape signature of one tiled-elementwise problem: the (rows, 128)
    flattened tile count plus the INPUT itemsize (quantize reads f32,
    dequantize reads int8 — different traffic per row)."""
    return "R%d-i%d" % (int(rows), int(itemsize))


def _quant_constraint(config, rows=None, in_itemsize=4, out_itemsize=1,
                      **_):
    from ..ops.pallas_kernels import quant_fits_vmem

    block = int(config["block"])
    if block < 1:
        return False
    if rows is not None:
        block = min(block, int(rows))
    return quant_fits_vmem(block, int(in_itemsize), int(out_itemsize))


register_space(TuningSpace(
    "quantize_int8_pallas",
    params={"block": (128, 256, 512, 1024, 2048)},
    default={"block": 512},  # the shipped min(rows, 512) cap
    constraint=_quant_constraint))

register_space(TuningSpace(
    "dequantize_int8_pallas",
    params={"block": (128, 256, 512, 1024, 2048)},
    default={"block": 512},
    constraint=_quant_constraint))


# -- fused_step_layout (non-kernel space, ISSUE 18) ---------------------------
def fused_step_sig(batch, dim, ndev):
    """Shape signature of one fused-step layout problem: batch × feature
    dim × device count (the layout trade — ZeRO shards over devices,
    prefetch hides host staging — is topology-dependent)."""
    return "B%d-D%d-dev%d" % (int(batch), int(dim), int(ndev))


def _fused_layout_constraint(config, mesh=False, **_):
    """ZeRO-1 only exists on the mesh path (``fused_step.py`` consults
    ``MXNET_FUSED_ZERO`` solely when the Module carries a mesh), so
    off-mesh candidates with ``zero=1`` would measure as silent no-ops —
    prune them instead of letting a meaningless tie pollute the store."""
    return not int(config.get("zero", 0)) or bool(mesh)


register_space(TuningSpace(
    "fused_step_layout",
    params={"zero": (0, 1), "prefetch": (0, 1, 2, 4)},
    default={"zero": 0, "prefetch": 2},  # io.PrefetchingIter's default depth
    constraint=_fused_layout_constraint))
