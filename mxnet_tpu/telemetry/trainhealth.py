"""Training health plane — in-graph grad/param statistics for the fused
step, runtime precision-verdict validation, rank-aware pod telemetry
(ISSUE 12 tentpole).

The fused Module step (``module/fused_step.py``) is the only training path
that matters at speed, and before this module its sole health signal was
the binary ``MXNET_NANCHECK`` flag.  With ``MXNET_TRAINHEALTH=1`` the same
donated jit also returns a compact stats pytree — global gradient norm,
per-parameter-group grad/param norms and update-to-weight ratios, the loss
head mean, and a per-group non-finite flag — all reduced on-device with
jnp ops (:func:`compute_step_stats`), so observing the step adds **zero
extra dispatches** and no host sync beyond the fit loop's existing metric
read (the stats materialize with the step outputs they share a dispatch
with).

The non-finite census is bucketed by the ISSUE 11 numerics verdict class
(``bf16_safe | fp32_accum | fp32_only``, via
``analysis.numerics.param_verdict_classes`` — each parameter group carries
the most conservative verdict among its consumer nodes).  A runtime
overflow inside a class the static analyzer *blessed* for reduced
precision is a first-class contradiction, counted in
``precision_verdict_violations_total{verdict}`` — the alertable signal
that the static CastPlan contract (PR 11) and runtime reality disagree.

The fit loop drains each step's stats into:

* the telemetry registry (``trainhealth_*`` gauges/counters, every sample
  labeled ``rank``),
* the JSONL event log (``kind: "trainhealth"``, ``rank`` field),
* a bounded in-process ring behind ``Module.trainer_stats()`` /
  :func:`status`, mirrored on the ops server's ``/statusz``,
* the flight recorder's event ring (one instant event per row), with a
  divergence (any non-finite group) triggering a crash dump that names the
  first offending group and carries the last N health rows.

Pod awareness: when ``jax.distributed`` is initialized, every sample and
JSONL line carries this process's ``rank``; each drain publishes a
``step:unix_ts`` heartbeat through the coordination-service KV store (the
same client ``parallel.dist.barrier`` uses), and **rank 0** aggregates
every rank's heartbeat into straggler/desync gauges —
``rank_step_lag_steps{rank}`` (how many steps a rank trails the
coordinator) and ``rank_heartbeat_age_seconds{rank}``.

Gating: :func:`plane` returns None when ``MXNET_TRAINHEALTH`` is unset —
call sites keep one ``is None`` check, and the fused jit's key and output
structure are byte-identical to a build without this module (the PR 1/4
zero-overhead contract, tested in ``tests/test_trainhealth.py``).
"""
from __future__ import annotations

import collections
import os
import threading
import time

from ..base import env_flag

__all__ = ["enabled", "ring_cap", "param_groups", "group_verdict_classes",
           "compute_step_stats", "HealthPlane", "plane", "status",
           "trainer_stats", "note_nonfinite_trip", "UNKNOWN_VERDICT",
           "BLESSED_VERDICTS", "DUMP_ROWS"]

# verdict-class strings are the PR 11 contract constants
# (analysis/numerics.py BF16_SAFE/FP32_ACCUM/FP32_ONLY); "unknown" is this
# module's fallback when the analyzer cannot classify (no avals, or the
# analysis itself failed — health must never fail a train step)
UNKNOWN_VERDICT = "unknown"
# classes the static analyzer blessed for reduced precision: a runtime
# non-finite there contradicts the CastPlan contract and counts into
# precision_verdict_violations_total{verdict}
BLESSED_VERDICTS = ("bf16_safe", "fp32_accum")
_VERDICT_RANK = {"bf16_safe": 0, "fp32_accum": 1, "fp32_only": 2,
                 UNKNOWN_VERDICT: 3}

DUMP_ROWS = 16  # recent health rows carried into a divergence crash dump

# parameter-name suffixes folded into one per-layer group (fc1_weight +
# fc1_bias -> group "fc1") — bounds the per-group series cardinality at
# one per layer instead of one per tensor
_GROUP_SUFFIXES = ("weight", "bias", "gamma", "beta")

_HB_PREFIX = "mxt_trainhealth/hb/"


def enabled():
    """``MXNET_TRAINHEALTH`` gate (docs/ENV_VARS.md) — default OFF."""
    return env_flag("MXNET_TRAINHEALTH")


def ring_cap():
    """Health rows kept in-process (``MXNET_TRAINHEALTH_RING``)."""
    try:
        v = int(os.environ.get("MXNET_TRAINHEALTH_RING", "256"))
    except ValueError:
        return 256
    return v if v > 0 else 256


def hb_interval_s():
    """Minimum seconds between pod heartbeat publishes/aggregations
    (``MXNET_TRAINHEALTH_HB_S``, default 1 — the slo.py ≤1/s discipline).
    The exchange is 2 blocking coordinator RPCs per rank (+ a dir scan on
    rank 0); unthrottled it would run once per training step.  ``0``
    publishes every drain (tests)."""
    try:
        return float(os.environ.get("MXNET_TRAINHEALTH_HB_S", "1"))
    except ValueError:
        return 1.0


def monitor_row_names(param_names):
    """The stat-row names the in-graph monitor route will feed for these
    parameters — ``Module.install_monitor`` matches a monitor's regex
    against this list to decide the route: a pattern that would match
    NOTHING here (e.g. ``fc1_weight``, a tensor name) keeps the un-jitted
    executor route instead of going silently blind."""
    names = []
    for group, _idxs in param_groups(param_names):
        for stat in ("grad_norm", "param_norm", "update_ratio"):
            names.append("%s:%s" % (group, stat))
    names.extend(["global:grad_norm", "loss"])
    return names


# -- static structure: groups + verdict classes -------------------------------
def param_groups(param_names):
    """Ordered ``((group_name, (param_index, ...)), ...)`` over the fused
    step's differentiable parameter list: params sharing a layer prefix
    (``fc1_weight``/``fc1_bias`` -> ``fc1``) form one group; anything
    without a known suffix is its own group."""
    order, members = [], {}
    for i, name in enumerate(param_names):
        group = name
        for suf in _GROUP_SUFFIXES:
            if name.endswith("_" + suf) and len(name) > len(suf) + 1:
                group = name[:-(len(suf) + 1)]
                break
        if group not in members:
            members[group] = []
            order.append(group)
        members[group].append(i)
    return tuple((g, tuple(members[g])) for g in order)


def group_verdict_classes(module, param_names, groups):
    """{group_name: verdict class} for a bound Module's train plan — each
    parameter takes the most conservative verdict among its consumer nodes
    (``analysis.numerics.param_verdict_classes``), each group the most
    conservative among its parameters.  Any analysis failure degrades to
    ``"unknown"`` for the affected groups: health must observe the step,
    never veto it."""
    per_param = {}
    try:
        from .. import analysis
        from ..analysis import numerics

        ctx = analysis.executor_context(module._exec, True)
        per_param = numerics.param_verdict_classes(ctx)
    except Exception:
        per_param = {}
    out = {}
    for group, idxs in groups:
        verdict = None
        for i in idxs:
            v = per_param.get(param_names[i])
            if v is None:
                continue
            if verdict is None or _VERDICT_RANK.get(v, 3) \
                    > _VERDICT_RANK.get(verdict, 3):
                verdict = v
        # a group none of whose params reach a classified node (e.g. all
        # consumers folded away) stays "unknown" — never silently "safe"
        out[group] = verdict if verdict is not None else UNKNOWN_VERDICT
    return out


# -- the traced stats reduction (runs INSIDE the fused jit) -------------------
def compute_step_stats(heads, grads, params, new_params, groups):
    """Build the health stats pytree from the fused step's own values —
    called inside ``_build_step_fn`` under ``jax.jit``, so every reduction
    here fuses into the one donated dispatch (no extra device round trip).

    Returns ``{"global_grad_norm", "loss", "grad_norm" (G,),
    "param_norm" (G,), "update_ratio" (G,), "nonfinite" (G,) bool,
    "heads_finite"}`` with G = len(groups).  ``param_norm`` is over the
    PRE-update weights, ``update_ratio`` = ||Δw|| / (||w|| + 1e-12) — the
    classic learning-rate sanity signal.  ``loss`` is the mean of the
    first output head: the loss itself for loss-head graphs
    (MakeLoss/fused detection), the mean prediction otherwise."""
    import jax.numpy as jnp

    f32 = jnp.float32
    gsq = [jnp.sum(jnp.square(g.astype(f32))) for g in grads]
    psq = [jnp.sum(jnp.square(w.astype(f32))) for w in params]
    usq = [jnp.sum(jnp.square(nw.astype(f32) - w.astype(f32)))
           for w, nw in zip(params, new_params)]
    gfin = [jnp.all(jnp.isfinite(g)) for g in grads]
    eps = jnp.asarray(1e-12, f32)

    def _tot(vals, idxs):
        tot = vals[idxs[0]]
        for i in idxs[1:]:
            tot = tot + vals[i]
        return tot

    gnorm, pnorm, ratio, nonfin = [], [], [], []
    for _name, idxs in groups:
        gnorm.append(jnp.sqrt(_tot(gsq, idxs)))
        p = jnp.sqrt(_tot(psq, idxs))
        pnorm.append(p)
        ratio.append(jnp.sqrt(_tot(usq, idxs)) / (p + eps))
        fin = gfin[idxs[0]]
        for i in idxs[1:]:
            fin = jnp.logical_and(fin, gfin[i])
        nonfin.append(jnp.logical_not(fin))
    total = gsq[0]
    for s in gsq[1:]:
        total = total + s
    heads_fin = jnp.bool_(True)
    for h in heads:
        heads_fin = jnp.logical_and(heads_fin, jnp.all(jnp.isfinite(h)))
    loss = (jnp.mean(heads[0].astype(f32)) if heads
            else jnp.asarray(0.0, f32))
    return {"global_grad_norm": jnp.sqrt(total), "loss": loss,
            "grad_norm": jnp.stack(gnorm), "param_norm": jnp.stack(pnorm),
            "update_ratio": jnp.stack(ratio), "nonfinite": jnp.stack(nonfin),
            "heads_finite": heads_fin}


# -- pod/rank plumbing --------------------------------------------------------
def _dist():
    """(coordination client or None, rank, world size) — (None, 0, 1) in
    single-process runs and whenever jax is absent/uninitialized.  Uses the
    same ``global_state.client`` handle ``parallel.dist.barrier`` does."""
    import sys

    if "jax" not in sys.modules:
        return None, 0, 1
    try:
        import jax

        n = jax.process_count()
        if n <= 1:
            return None, 0, 1
        client = getattr(jax._src.distributed.global_state, "client", None)
        return client, jax.process_index(), n
    except Exception:
        return None, 0, 1


def _publish_heartbeat(client, rank, drains):
    """Write this rank's ``drain_count:unix_ts`` heartbeat into the
    coordination KV store (the plane's monotonic drain counter, which
    unlike the stepper's step count survives stale()-rebuilds).  Keys are
    single-use in TSL, so delete-then-set; every failure is swallowed — a
    flaky coordinator must not fail training."""
    key = _HB_PREFIX + str(rank)
    try:
        client.key_value_delete(key)
    except Exception:
        pass
    try:
        client.key_value_set(key, "%d:%.3f" % (int(drains), time.time()))
    except Exception:
        pass


def _read_heartbeats(client):
    """{rank: (drain count, unix_ts)} for every rank that has published —
    one shared KV prefix scan (``parallel.dist.kv_prefix_ranks``, the same
    one the dead-node check uses)."""
    from ..parallel.dist import kv_prefix_ranks

    out = {}
    for rk, value in kv_prefix_ranks(client, _HB_PREFIX).items():
        try:
            s, ts = str(value).split(":", 1)
            out[rk] = (int(s), float(ts))
        except (ValueError, TypeError):
            pass
    return out


def _safe(x):
    """float(x) when finite, else None — everything the plane hands to
    json consumers (the JSONL sink, flightrec dumps) must stay strict
    JSON: python's encoder emits bare ``NaN``/``Infinity`` tokens that
    spec-compliant parsers (Perfetto's JSON.parse import, jq) reject — and
    a divergence, the one event the dump exists for, is exactly when these
    values go non-finite.  The per-group ``nonfinite`` flags and the
    census stay the authoritative divergence signal."""
    import math

    x = float(x)
    return x if math.isfinite(x) else None


# -- the host-side plane ------------------------------------------------------
class HealthPlane:
    """Per-process drain target: converts the step's device stats into one
    host row, feeds registry/JSONL/flight-recorder, keeps the bounded ring
    behind ``trainer_stats()``/``status()``, and runs the pod heartbeat
    exchange.  One per process (mirrors ``flightrec.recorder``)."""

    def __init__(self, cap=None):
        self._ring = collections.deque(maxlen=cap or ring_cap())
        self._mu = threading.Lock()
        self._last = None
        self._ranks = None   # rank 0: {rank: {step, lag_steps, hb age}}
        self._trips = 0
        # monotonic drain counter — the heartbeat/straggler baseline.
        # Deliberately NOT the stepper's _nsteps: that resets on every
        # stale() rebuild (optimizer swap, gate flip), which would read
        # as a false straggler page (or mask a real one on rank 0).
        self._drained = 0
        self._last_hb = None  # monotonic of the last heartbeat exchange

    # -- drain (called once per fit-loop batch, after the metric sync) -------
    def drain(self, module, epoch=None, step=None):
        """Pop the fused stepper's pending stats and fan them out → the
        host row dict, or None when the module has none staged (legacy
        path, or no step ran).  The device reads here cost nothing extra:
        the stats share a dispatch with the step outputs the metric read
        already synced."""
        fused = getattr(module, "_fused", None)
        raw = fused.pop_health() if fused is not None else None
        if raw is None:
            return None
        t0 = time.perf_counter()
        import numpy as np

        stepno, stats = raw
        groups = fused._health_groups or ()
        verdicts = fused._health_verdicts or {}
        names = [g for g, _ in groups]
        gn = np.asarray(stats["grad_norm"], dtype=np.float64)
        pn = np.asarray(stats["param_norm"], dtype=np.float64)
        ur = np.asarray(stats["update_ratio"], dtype=np.float64)
        nf = np.asarray(stats["nonfinite"], dtype=bool)
        ggn = float(np.asarray(stats["global_grad_norm"]))
        loss = float(np.asarray(stats["loss"]))
        heads_ok = bool(np.asarray(stats["heads_finite"]))
        client, rank, size = _dist()

        bad = [names[i] for i in range(len(names)) if nf[i]]
        census = {}
        for g in bad:
            v = verdicts.get(g, UNKNOWN_VERDICT)
            census[v] = census.get(v, 0) + 1
        # every float in the row is JSON-safe (_safe: non-finite -> None);
        # the nonfinite flags/census carry the divergence signal
        row = {
            "step": int(stepno), "epoch": epoch, "fit_step": step,
            "rank": int(rank),
            "global_grad_norm": _safe(ggn), "loss": _safe(loss),
            "heads_finite": heads_ok,
            "groups": {
                names[i]: {"grad_norm": _safe(gn[i]),
                           "param_norm": _safe(pn[i]),
                           "update_ratio": _safe(ur[i]),
                           "nonfinite": bool(nf[i]),
                           "verdict": verdicts.get(names[i],
                                                   UNKNOWN_VERDICT)}
                for i in range(len(names))},
            "nonfinite_groups": bad,
            "nonfinite_census": census,
        }
        with self._mu:
            self._ring.append(row)
            self._last = row
            self._drained += 1
            drained = self._drained
        self._feed_registry(row)
        from . import instrument

        instrument.event(
            "trainhealth", rank=row["rank"], step=row["step"],
            epoch=epoch, global_grad_norm=row["global_grad_norm"],
            loss=row["loss"], heads_finite=heads_ok,
            groups=row["groups"], nonfinite_census=census)
        from . import flightrec

        frec = flightrec.recorder()
        if frec is not None:
            frec.record("trainhealth", step=row["step"], rank=row["rank"],
                        global_grad_norm=row["global_grad_norm"],
                        loss=row["loss"], nonfinite=bad)
        if client is not None:
            # throttled: heartbeats need ~1/s resolution, not one blocking
            # coordinator RPC pair per training step (hb_interval_s)
            mono = time.monotonic()
            if self._last_hb is None \
                    or mono - self._last_hb >= hb_interval_s():
                self._last_hb = mono
                _publish_heartbeat(client, rank, drained)
                if rank == 0:
                    self._aggregate(client, size, drained)
        if bad or not heads_ok:
            self._trip(row, frec)
        if instrument.enabled():
            instrument.registry().counter(
                "trainhealth_drain_seconds_total",
                "host wall seconds spent draining health stats — the "
                "plane's whole per-step overhead beyond the in-graph "
                "reductions", ("rank",)).inc(
                max(0.0, time.perf_counter() - t0), rank=str(rank))
        return row

    def _feed_registry(self, row):
        from . import instrument

        if not instrument.enabled():
            return
        r = instrument.registry()
        lr = str(row["rank"])

        def _set(gauge, value, **labels):
            if value is not None:  # _safe()'d a non-finite: gauge holds
                gauge.set(value, **labels)  # its last finite reading

        _set(r.gauge("trainhealth_global_grad_norm",
                     "global L2 gradient norm of the last fused step",
                     ("rank",)), row["global_grad_norm"], rank=lr)
        _set(r.gauge("trainhealth_loss",
                     "first-head mean of the last fused step", ("rank",)),
             row["loss"], rank=lr)
        gg = r.gauge("trainhealth_group_grad_norm",
                     "per-parameter-group L2 gradient norm",
                     ("group", "rank"))
        gp = r.gauge("trainhealth_group_param_norm",
                     "per-parameter-group L2 weight norm (pre-update)",
                     ("group", "rank"))
        gu = r.gauge("trainhealth_group_update_ratio",
                     "per-parameter-group ||delta w|| / ||w||",
                     ("group", "rank"))
        for g, s in row["groups"].items():
            _set(gg, s["grad_norm"], group=g, rank=lr)
            _set(gp, s["param_norm"], group=g, rank=lr)
            _set(gu, s["update_ratio"], group=g, rank=lr)
        r.counter("trainhealth_rows_total", "health rows drained",
                  ("rank",)).inc(rank=lr)
        if row["nonfinite_census"]:
            nft = r.counter(
                "trainhealth_nonfinite_total",
                "parameter groups with non-finite gradients, bucketed by "
                "their static numerics verdict class",
                ("verdict", "rank"))
            pvv = r.counter(
                "precision_verdict_violations_total",
                "runtime non-finite in a verdict class the static "
                "numerics analyzer blessed for reduced precision — the "
                "CastPlan contract and runtime reality disagree; alert on "
                "any nonzero rate", ("verdict", "rank"))
            for v, n in row["nonfinite_census"].items():
                nft.inc(n, verdict=v, rank=lr)
                if v in BLESSED_VERDICTS:
                    pvv.inc(n, verdict=v, rank=lr)

    def _aggregate(self, client, size, my_drains):
        """Rank 0: fold every rank's heartbeat into straggler gauges —
        lag is measured in DRAINS (one per fit-loop batch), against this
        plane's own monotonic counter."""
        from . import instrument

        now = time.time()
        hbs = _read_heartbeats(client)
        r = instrument.registry() if instrument.enabled() else None
        agg = {}
        for rk in range(size):
            st, ts = hbs.get(rk, (None, None))
            lag = None if st is None else max(0, int(my_drains) - st)
            age = None if ts is None else max(0.0, now - ts)
            agg[rk] = {"drains": st, "lag_steps": lag,
                       "heartbeat_age_s": None if age is None
                       else round(age, 3)}
            if r is not None and lag is not None:
                r.gauge("rank_step_lag_steps",
                        "steps this rank trails rank 0's last health "
                        "drain — a persistent nonzero value is a "
                        "straggler or a desynced loop",
                        ("rank",)).set(lag, rank=str(rk))
            if r is not None and age is not None:
                r.gauge("rank_heartbeat_age_seconds",
                        "seconds since this rank's last health heartbeat",
                        ("rank",)).set(age, rank=str(rk))
        with self._mu:
            self._ranks = agg

    def _trip(self, row, frec):
        """A divergence: name the first non-finite group and dump the
        flight recorder (auto-throttled per reason like every other
        trigger).  The plane records and alerts — ``MXNET_NANCHECK`` is
        the path that *raises*."""
        with self._mu:
            self._trips += 1
            recent = list(self._ring)[-DUMP_ROWS:]
        first = (row["nonfinite_groups"][0] if row["nonfinite_groups"]
                 else "<heads>")
        verdict = row["groups"].get(first, {}).get("verdict",
                                                   UNKNOWN_VERDICT)
        from . import instrument

        instrument.event("trainhealth_trip", rank=row["rank"],
                         step=row["step"], group=first, verdict=verdict)
        if frec is not None:
            frec.dump("trainhealth", auto=True, group=first,
                      verdict=verdict, step=row["step"], rank=row["rank"],
                      health_rows=recent)

    # -- read surfaces -------------------------------------------------------
    def last_row(self):
        with self._mu:
            return self._last

    def rows(self):
        with self._mu:
            return list(self._ring)

    def status(self):
        """The ``/statusz`` block: last row + per-rank heartbeat view."""
        with self._mu:
            return {"last": self._last, "rows": len(self._ring),
                    "trips": self._trips, "ranks": self._ranks}


# -- process-global plane (mirrors flightrec.recorder) ------------------------
_mu = threading.Lock()
_plane = None


def plane():
    """The process HealthPlane, or None when ``MXNET_TRAINHEALTH`` is
    unset — the caller's one-check gate."""
    global _plane
    if not enabled():
        return None
    with _mu:
        if _plane is None:
            _plane = HealthPlane()
        return _plane


def status():
    """``/statusz``/CLI surface: the plane's status dict, or None when the
    gate is off (distinguishable from an enabled-but-idle plane)."""
    with _mu:
        p = _plane
    if p is None:
        return None if not enabled() else plane().status()
    return p.status()


def trainer_stats():
    """The last drained health row (host floats), or None — the surface
    behind ``Module.trainer_stats()``.  Authoritative without telemetry,
    like ``Engine.stats()``."""
    with _mu:
        p = _plane
    return p.last_row() if p is not None else None


def _reset_for_tests():
    global _plane
    with _mu:
        _plane = None


# -- MXNET_NANCHECK flight-recorder wiring (ISSUE 12 satellite) ---------------
def note_nonfinite_trip(where, step, detail=None):
    """A nancheck trip is about to raise: push the context into the flight
    recorder and dump — the post-mortem for a divergence now includes the
    recent request/step timeline plus the last health rows (when the
    trainhealth plane is live).  Explicit dump (never throttled): a raise
    follows, there is no second chance to write the black box."""
    from . import flightrec

    frec = flightrec.recorder()
    if frec is None:
        return None
    frec.record("nancheck", where=where, step=step,
                detail=detail or "")
    with _mu:
        p = _plane
    rows = p.rows()[-DUMP_ROWS:] if p is not None else []
    return frec.dump("nancheck", where=where, step=step,
                     detail=detail or "", health_rows=rows)
