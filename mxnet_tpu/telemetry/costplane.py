"""Compile plane — per-executable XLA cost/memory ledger (ISSUE 13).

The ops plane (ISSUE 10) watches requests, the health plane (ISSUE 12)
watches gradients; this plane watches the **compiler**.  Every compile
site — ``compile_cache.CachedFunction``, ``Executor._compiled`` (and so
``Predictor`` and every serving warmup bucket), ``FusedStepper`` — records
one row per executable XLA actually built: logical key, arg-shape
signature, pass/numerics/autotune fingerprints, backend + device kind,
compile seconds, ``compiled.cost_analysis()`` flops/bytes and
``compiled.memory_analysis()`` temp/arg/output/peak bytes.  A graph-pass
or autotune change that silently doubles a module's FLOPs or peak HBM
becomes a visible delta instead of a mystery regression, and the measured
rows are the training set ROADMAP item 4's learned cost model seeds from
(PAPERS.md 1805.08166 / 1802.04799: TVM's predict-then-measure loop needs
measured cost features per program).

Everything gates on ``MXNET_COSTPLANE`` (docs/ENV_VARS.md) with the PR
1/4/10/12 zero-overhead contract: unset ⇒ every helper is a no-op behind
one env read, jits stay plain (no AOT split), AOT-cache keys are untouched,
and no ledger I/O happens (tested in tests/test_costplane.py).  The
executor's per-node ``named_scope``s are unconditional (``executor.py``
``run_node``) and no part of this plane.

Surfaces, gate on:

* process-local bounded ring (:func:`rows` / :func:`status` /
  :func:`totals`) — always available, no telemetry required (the
  ``compile_cache.stats`` stance);
* registry counters ``compile_rows_total{site}`` /
  ``costplane_partial_total{surface}`` / ``costplane_drift_total{kernel}``
  and a JSONL ``kind: "compile"`` event per row when ``MXNET_TELEMETRY``
  is on;
* ``Engine.stats()["costplane"]`` and the ``/statusz`` "costplane" block;
* per-bucket ``xla_flops`` / ``xla_peak_bytes`` warmup report columns;
* a persistent **ledger** at ``$MXNET_COST_LEDGER`` (JSONL, one row per
  compile, keyed by a stable fingerprint of site + logical key + shape
  signature) that ``tools/bench_compare.py --gate-cost`` diffs across
  builds — compiler regressions gate CI the way pass-drift already gates
  plan-shape changes — and ``tools/trace_summary.py --ledger`` reads for
  roofline module totals.

**Degradation contract.**  ``cost_analysis()`` / ``memory_analysis()``
returning None, raising, or missing keys (CPU backends, exotic runtimes)
yields a PARTIAL row — numeric fields null, ``partial`` naming the
surface that failed — never a crash and never a dropped row (tested).

**Declared-vs-measured cross-check.**  The PR 1 Pallas cost registry
*declares* per-kernel FLOPs/bytes at trace time; XLA *measures* the
module that contains them.  Each row snapshots which registered kernels
were traced while lowering that executable and checks the declared
totals against the measured module totals: a kernel whose declared
FLOPs/bytes exceed what XLA measured for the whole module is an inflated
declaration (XLA's totals include every custom-call operand, so they
dominate any honest kernel declaration) — counted per kernel in
``costplane_drift_total{kernel}`` and named in the row's ``drift`` list,
the pass-drift contract applied to cost metadata.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
import weakref

from ..base import env_flag

__all__ = ["enabled", "ledger_path", "extract", "record_compile",
           "kernel_snapshot", "kernel_delta", "open_trace_bracket",
           "close_trace_bracket", "crosscheck", "rows",
           "row_count", "rows_since", "totals", "status", "instrument_jit",
           "candidate_features", "load_ledger"]

_RING_MAX = 512  # rows kept in-process; the ledger file holds everything

_mu = threading.Lock()
_rows = []          # bounded ring of row dicts (insertion order)
_n_rows = 0         # monotonic row counter (ring evictions don't rewind it)
_partial = {}       # surface -> count
_drift = {}         # kernel -> count
_ledger_failed = False


def enabled():
    """``MXNET_COSTPLANE`` gate — read per call so tests can flip it."""
    return env_flag("MXNET_COSTPLANE")


def ledger_path():
    """``MXNET_COST_LEDGER`` file, or None (rows then stay in-process)."""
    p = os.environ.get("MXNET_COST_LEDGER", "").strip()
    return p or None


def _reset_for_tests():
    global _n_rows, _ledger_failed
    with _mu:
        _rows[:] = []
        _n_rows = 0
        _partial.clear()
        _drift.clear()
        _ledger_failed = False


# -- extraction ---------------------------------------------------------------
def _int_or_none(v):
    try:
        if v is None or isinstance(v, bool):
            return None
        f = float(v)
        if f != f or f in (float("inf"), float("-inf")) or f < 0:
            return None
        return int(f)
    except (TypeError, ValueError):
        return None


def extract(compiled):
    """Pull cost/memory features off one compiled executable →
    ``(features, partial)``.

    ``features``: flops, transcendentals, bytes_accessed (cost analysis)
    and temp/arg/output/generated-code/peak bytes (memory analysis), each
    None when the backend does not report it.  ``partial`` lists the
    surfaces ("cost", "memory") that returned nothing usable — a backend
    may support one, both, or neither, and every combination must produce
    a row (the degradation tests feed stubs that return None, raise, and
    drop keys)."""
    feat = {"flops": None, "transcendentals": None, "bytes_accessed": None,
            "temp_bytes": None, "arg_bytes": None, "output_bytes": None,
            "generated_code_bytes": None, "peak_bytes": None}
    partial = []
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            feat["flops"] = _int_or_none(ca.get("flops"))
            feat["transcendentals"] = _int_or_none(ca.get("transcendentals"))
            feat["bytes_accessed"] = _int_or_none(
                ca.get("bytes accessed", ca.get("bytes_accessed")))
        if feat["flops"] is None and feat["bytes_accessed"] is None:
            partial.append("cost")
    except Exception:
        partial.append("cost")
    try:
        ma = compiled.memory_analysis()
        for attr, key in (("temp_size_in_bytes", "temp_bytes"),
                          ("argument_size_in_bytes", "arg_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("generated_code_size_in_bytes",
                           "generated_code_bytes")):
            feat[key] = _int_or_none(getattr(ma, attr, None))
        # peak = the executable's device-memory high-water proxy: arguments
        # + outputs + temporaries (XLA's CompiledMemoryStats exposes the
        # components, not the schedule's true peak; the sum is its upper
        # bound and moves with the same regressions)
        parts = [feat["temp_bytes"], feat["arg_bytes"], feat["output_bytes"]]
        if all(p is not None for p in parts):
            feat["peak_bytes"] = sum(parts)
        if all(feat[k] is None for k in
               ("temp_bytes", "arg_bytes", "output_bytes")):
            partial.append("memory")
    except Exception:
        partial.append("memory")
    return feat, partial


# -- declared-vs-measured cross-check ----------------------------------------
def kernel_snapshot():
    """{kernel: calls} from the Pallas cost registry, for bracketing one
    trace/lower (→ :func:`kernel_delta`).  {} when the registry is
    unavailable — the plane must work in processes that never import ops."""
    try:
        from ..ops import pallas_kernels

        return {k: v["calls"] for k, v in pallas_kernels.traced_costs()
                .items()}
    except Exception:
        return {}


class _TraceBracket:
    """One trace/lower window's registry snapshot.  The traced-costs
    registry is process-global, so a bracket whose window OVERLAPS another
    open bracket (the warmup thread pool lowers many buckets concurrently)
    cannot attribute new kernel calls to its own executable — overlapping
    brackets mark each other ``dirty`` and their delta degrades to {}
    (no declared row, no drift check) instead of cross-attributing other
    executables' kernels and raising false drift alarms."""

    __slots__ = ("snap", "dirty", "delta", "__weakref__")


# open brackets, weakly held: a lower whose finalize never runs (caller
# dropped the handle) must not poison every future bracket
_open_brackets = weakref.WeakSet()


def open_trace_bracket():
    """Begin bracketing one trace/lower → token for :func:`kernel_delta` /
    :func:`close_trace_bracket`, or None with the gate off."""
    if not enabled():
        return None
    tok = _TraceBracket()
    tok.delta = None
    with _mu:
        tok.dirty = bool(_open_brackets)
        if tok.dirty:
            for other in _open_brackets:
                other.dirty = True
        _open_brackets.add(tok)
    tok.snap = None if tok.dirty else kernel_snapshot()
    return tok


def close_trace_bracket(token):
    """End a bracket (idempotent).  The delta is computed HERE, at the end
    of the trace window — a lower that starts after this close can no
    longer leak its kernels into this token's attribution."""
    if token is None:
        return
    with _mu:
        _open_brackets.discard(token)
    if token.delta is None:
        token.delta = ({} if (token.dirty or token.snap is None)
                       else _delta_since(token.snap))


def _delta_since(snapshot):
    out = {}
    try:
        from ..ops import pallas_kernels

        for name, ent in pallas_kernels.traced_costs().items():
            new = ent["calls"] - snapshot.get(name, 0)
            if new > 0:
                out[name] = {"calls": new, "flops": ent["flops"],
                             "bytes": ent["bytes_accessed"]}
    except Exception:
        return {}
    return out


def kernel_delta(token):
    """Kernels traced inside one bracket →
    ``{kernel: {"calls", "flops", "bytes"}}`` with per-invocation declared
    costs; {} when nothing new traced, no bracket was taken, or the
    bracket's window overlapped another lower (attribution impossible).
    A plain ``{kernel: calls}`` snapshot dict is also accepted (tests,
    single-threaded callers)."""
    if token is None:
        return {}
    if isinstance(token, _TraceBracket):
        close_trace_bracket(token)
        return dict(token.delta)
    return _delta_since(token)


def crosscheck(feat, declared):
    """→ sorted kernels whose DECLARED totals exceed the MEASURED module
    totals — impossible for an honest declaration (the module contains the
    kernel's operand traffic and every other op), so it marks a drifted
    cost model.  Skipped per axis when the backend measured nothing."""
    bad = set()
    for name, d in (declared or {}).items():
        if feat.get("flops") and d["flops"] * d["calls"] > feat["flops"]:
            bad.add(name)
        if feat.get("bytes_accessed") \
                and d["bytes"] * d["calls"] > feat["bytes_accessed"]:
            bad.add(name)
    return sorted(bad)


# -- row assembly -------------------------------------------------------------
def _fingerprints():
    """The program-shaping fingerprints in force when this executable was
    built — the same identities the AOT cache verifies (compile_cache
    ``_env_fingerprint``), so a ledger diff can tell "the compiler changed
    the program" from "we asked for a different program".  Best-effort:
    each piece degrades to None independently."""
    fp = {"passes": None, "numerics": None, "autotune": None}
    try:
        from .. import graph_passes

        fp["passes"] = "|".join("%s:%d" % nv
                                for nv in graph_passes.pipeline())
    except Exception:
        pass
    try:
        from ..analysis import numerics

        fp["numerics"] = numerics.contract_fingerprint()
    except Exception:
        pass
    try:
        if env_flag("MXNET_AUTOTUNE"):
            from ..autotune import store as _at_store

            fp["autotune"] = _at_store.state_digest()
    except Exception:
        pass
    return fp


def _backend():
    try:
        import jax

        devs = jax.devices()
        return jax.default_backend(), str(devs[0].device_kind)
    except Exception:
        return None, None


def row_key(site, key, sig):
    """Stable cross-run row identity: same code + same logical key + same
    shapes hash to the same ledger key, so two builds' ledgers diff
    row-for-row."""
    h = hashlib.sha256(repr((str(site), str(key),
                             str(sig))).encode("utf-8")).hexdigest()[:16]
    return "%s-%s" % (site, h)


def record_compile(site, key, sig, compiled, compile_s, tc0=None):
    """Record one freshly-built executable (the ONE entry point every
    compile site calls).  No-op when the gate is off; never raises —
    a cost-accounting problem must not fail the compile it observed."""
    if not enabled():
        return None
    try:
        return _record(site, key, sig, compiled, compile_s, tc0)
    except Exception:
        return None


def cost_fingerprint(compiled):
    """flops/bytes identity of one compiled executable, for persisting
    alongside an AOT-cache entry (compile_cache ``_store``) → dict or
    None.  Captured at store time — ``deserialize_and_load`` results may
    not answer ``cost_analysis`` — so a restore's ledger row carries the
    program's identity as compiled.  Never raises."""
    try:
        feat, _ = extract(compiled)
        return {"flops": feat.get("flops"),
                "bytes_accessed": feat.get("bytes_accessed")}
    except Exception:
        return None


def record_restore(site, key, sig, cost=None):
    """Ledger row for an executable RESTORED from the AOT cache (ISSUE
    20): ``compile_s`` 0.0, cost identity from the entry's stored
    fingerprint.  A warm pod restart thus still publishes per-rank rows
    the cross-rank ledger-divergence detector can diff — "every rank
    restored the identical program" becomes checkable, not assumed.
    ``kind`` is ``"restore"`` so :func:`load_ledger` (a diff of what was
    *built*) keeps skipping these.  No-op when the gate is off; never
    raises."""
    if not enabled():
        return None
    try:
        global _n_rows
        backend, device_kind = _backend()
        row = {"kind": "restore", "key": row_key(site, key, sig),
               "site": str(site), "logical_key": str(key), "sig": str(sig),
               "backend": backend, "device_kind": device_kind,
               "fingerprints": _fingerprints(), "compile_s": 0.0,
               "flops": (cost or {}).get("flops"),
               "bytes_accessed": (cost or {}).get("bytes_accessed"),
               "peak_bytes": None,  # totals() reads it on every row
               "partial": [] if cost else ["cost"],
               "declared": None, "drift": [],
               "unix_ts": round(time.time(), 3)}
        with _mu:
            _rows.append(row)
            del _rows[:-_RING_MAX]
            _n_rows += 1
        _append_ledger(row)
        from . import instrument

        if instrument.enabled():
            instrument.registry().counter(
                "compile_rows_total",
                "executables the compile plane recorded",
                ("site",)).inc(site=row["site"])
        return row
    except Exception:
        return None


def _record(site, key, sig, compiled, compile_s, tc0):
    global _n_rows
    feat, partial = extract(compiled)
    declared = kernel_delta(tc0)
    drift = crosscheck(feat, declared)
    backend, device_kind = _backend()
    row = {"kind": "compile", "key": row_key(site, key, sig),
           "site": str(site), "logical_key": str(key), "sig": str(sig),
           "backend": backend, "device_kind": device_kind,
           "fingerprints": _fingerprints(),
           "compile_s": round(float(compile_s), 4)}
    row.update(feat)
    row["partial"] = partial
    row["declared"] = declared or None
    row["drift"] = drift
    row["unix_ts"] = round(time.time(), 3)
    with _mu:
        _rows.append(row)
        del _rows[:-_RING_MAX]
        _n_rows += 1
        for s in partial:
            _partial[s] = _partial.get(s, 0) + 1
        for k in drift:
            _drift[k] = _drift.get(k, 0) + 1
    _append_ledger(row)
    from . import instrument

    if instrument.enabled():
        r = instrument.registry()
        r.counter("compile_rows_total",
                  "executables the compile plane recorded", ("site",)).inc(
                      site=row["site"])
        for s in partial:
            r.counter("costplane_partial_total",
                      "cost/memory analysis surfaces that reported nothing "
                      "for a compiled executable (each a partial row)",
                      ("surface",)).inc(surface=s)
        for k in drift:
            r.counter("costplane_drift_total",
                      "Pallas kernels whose declared FLOPs/bytes exceeded "
                      "the measured module totals (inflated cost model)",
                      ("kernel",)).inc(kernel=k)
        r.event("compile", **{k: row[k] for k in
                              ("key", "site", "sig", "backend",
                               "device_kind", "compile_s", "flops",
                               "bytes_accessed", "temp_bytes", "arg_bytes",
                               "output_bytes", "peak_bytes", "partial",
                               "drift")})
    return row


def _append_ledger(row):
    """One JSONL line per row; a write failure warns once and disables the
    ledger (the JsonlSink stance) — in-process surfaces keep working."""
    global _ledger_failed
    path = ledger_path()
    if path is None or _ledger_failed:
        return
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    except OSError:
        _ledger_failed = True
        import logging

        logging.warning("costplane: cannot append to MXNET_COST_LEDGER=%r "
                        "— ledger disabled for this process", path)


def load_ledger(path):
    """Parse a ledger file → {key: row}, LAST row per key wins (a key
    recompiled during one run supersedes its earlier rows).  Unparseable
    and non-compile lines are skipped — a ledger must never crash its
    reader."""
    out = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict) and row.get("kind") == "compile" \
                    and "key" in row:
                out[row["key"]] = row
    return out


# -- in-process surfaces ------------------------------------------------------
def rows():
    """Snapshot of the in-process row ring (most recent ``_RING_MAX``)."""
    with _mu:
        return [dict(r) for r in _rows]


def row_count():
    """Monotonic count of rows recorded by this process."""
    with _mu:
        return _n_rows


def rows_since(n, site=None):
    """Rows recorded after monotonic count ``n`` (optionally one site) —
    how the serving warmup attributes compile rows to the bucket it just
    warmed.  Rows evicted from the ring before the read are gone (the
    ring far outlasts one warmup pass)."""
    with _mu:
        start = len(_rows) - (_n_rows - n)
        got = [dict(r) for r in _rows[max(0, start):]]
    if site is not None:
        got = [r for r in got if r["site"] == site]
    return got


def totals():
    """Process aggregate → ``{"flops", "peak_bytes", "rows"}`` — flops
    summed and peak maxed over rows that reported them; both None when no
    row carried the number (backend can't report, or no compiles yet).
    The bench telemetry block's ``xla_flops`` / ``xla_peak_bytes``."""
    with _mu:
        fl = [r["flops"] for r in _rows if r["flops"] is not None]
        pk = [r["peak_bytes"] for r in _rows if r["peak_bytes"] is not None]
        n = _n_rows
    return {"flops": sum(fl) if fl else None,
            "peak_bytes": max(pk) if pk else None, "rows": n}


def status():
    """The ``Engine.stats()["costplane"]`` / ``/statusz`` block: row and
    degradation counts, per-site row split, flop/peak aggregates, and the
    most recent row."""
    with _mu:
        by_site = {}
        for r in _rows:
            by_site[r["site"]] = by_site.get(r["site"], 0) + 1
        last = dict(_rows[-1]) if _rows else None
        out = {"rows": _n_rows, "by_site": by_site,
               "partial": dict(_partial), "drift": dict(_drift),
               "ledger": ledger_path() if not _ledger_failed else None,
               "last": last}
    t = totals()
    out["flops_total"] = t["flops"]
    out["peak_bytes_max"] = t["peak_bytes"]
    return out


# -- plain-jit instrumentation ------------------------------------------------
class _InstrumentedJit:
    """AOT split (``lower().compile()``) around a plain jitted callable so
    uncached compile sites still produce ledger rows — the gate-on sibling
    of ``compile_cache.CachedFunction`` minus persistence.  Dispatches
    through the compiled executable per signature; any failure degrades to
    the wrapped jit (slower, never wrong) EXCEPT dispatch errors under
    donation, where the executable may already have consumed its donated
    buffers (the compile_cache stance) — those re-raise."""

    def __init__(self, jit_fn, site, key, donated=False):
        self._jit = jit_fn
        self._site = str(site)
        self._key = repr(tuple(key))
        self._donated = bool(donated)
        self._exes = {}
        self._lock = threading.Lock()
        self.__wrapped__ = jit_fn

    def _cache_size(self):  # instrument_step's compile detector reads this
        return len(self._exes)

    def __call__(self, *args):
        from .. import compile_cache

        sig = compile_cache.CachedFunction._sig(args)
        exe = self._exes.get(sig)
        if exe is None:
            import time as _time

            # compile under the lock (double-checked): two threads racing a
            # new signature must not both pay the XLA compile and both
            # record a ledger row for one executable
            with self._lock:
                exe = self._exes.get(sig)
                if exe is None:
                    tc0 = open_trace_bracket()
                    try:
                        t0 = _time.perf_counter()
                        lowered = self._jit.lower(*args)
                        close_trace_bracket(tc0)  # trace window ends here
                        compiled = lowered.compile()
                        dt = _time.perf_counter() - t0
                        record_compile(
                            self._site, self._key,
                            compile_cache.CachedFunction._sig_str(sig),
                            compiled, dt, tc0=tc0)
                        self._exes[sig] = compiled
                        exe = compiled
                    except Exception:
                        return self._jit(*args)  # unrecordable ≠ unrunnable
                    finally:
                        close_trace_bracket(tc0)
        try:
            return exe(*args)
        except Exception:
            with self._lock:
                self._exes.pop(sig, None)
            if self._donated:
                raise
            return self._jit(*args)


def instrument_jit(jit_fn, site, key, donated=False):
    """Wrap a jitted callable so each new shape signature records a compile
    row.  Callers guard with :func:`enabled` — with the gate off they keep
    the plain jit and this module never runs."""
    return _InstrumentedJit(jit_fn, site, key, donated=donated)


def candidate_features(fn, args):
    """Measured cost features for one autotune trial candidate (ISSUE 13
    item 4): AOT-compile the candidate and extract flops/bytes/peak — the
    per-config feature vector the learned cost model trains on.  → small
    dict or None on ANY problem (a candidate that can't report features
    still gets timed).  The extra compile is absorbed by the measurer's
    warmup calls; only runs under the gate (caller-checked).

    ISSUE 18 widened the vector with two model features: ``compile_s``
    (lower+compile wall seconds — compile cost is itself a latency the
    ranker should know) and ``drift``, the count of Pallas kernels whose
    DECLARED totals exceed the candidate's measured module totals inside
    this trace's bracket (``crosscheck``) — a distrust signal that lets
    the fit discount ledger rows backed by a drifted cost model.  The
    bracket degrades to drift=0 when another lower overlaps (same
    no-cross-attribution contract as compile rows)."""
    tok = None
    try:
        t0 = time.perf_counter()
        tok = open_trace_bracket()
        lowered = fn.lower(*args)
        declared = kernel_delta(tok)  # closes the bracket at trace end
        tok = None
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        feat, _partial = extract(compiled)
        return {"flops": feat["flops"],
                "bytes_accessed": feat["bytes_accessed"],
                "temp_bytes": feat["temp_bytes"],
                "peak_bytes": feat["peak_bytes"],
                "compile_s": round(compile_s, 4),
                "drift": len(crosscheck(feat, declared))}
    except Exception:
        return None
    finally:
        close_trace_bracket(tok)
