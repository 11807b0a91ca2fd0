"""AOT compilation + persistent executable cache (ISSUE 6).

Every process restart used to re-trace and re-compile the whole serving
bucket ladder and the fused train step from scratch.  This module makes a
restart a disk read instead of a compile storm — the TVM/Relay ahead-of-time
deployment story (PAPERS.md 1802.04799 / 1904.08368) mapped onto XLA: the
workload already specializes to a FINITE signature set (bucket ladder,
fused-step shape signatures), so the executables can be built once and
persisted.

The ``MXNET_AOT_CACHE=<dir>`` tiers (unset ⇒ tier 1 is inert and the jit
paths are byte-identical to a build without it):

* **tier 1 — explicit executable cache.**  :class:`CachedFunction` wraps an
  already-jitted callable.  Per argument-shape signature it splits the AOT
  pipeline ``jax.jit(fn).lower(*args).compile()`` — so warmup can run the
  trace/lower stage for many signatures concurrently off the device loop —
  and persists the finished executable via
  ``jax.experimental.serialize_executable`` to ``<dir>/exec/<name>-<sha>.jx``.
  A warm restart deserializes the executable: no trace, no lower, no XLA
  compile.  Each entry stores an **environment fingerprint** (jax + jaxlib
  versions, backend kind, device kind/count, mesh descriptor) and the full
  logical key; any mismatch, truncated file, or deserialize failure is a
  SILENT miss — counted in ``aot_cache_errors_total{reason}`` — and the
  entry is recompiled and overwritten, never a crash.
* **tier 2 — JAX's persistent compilation cache, in whatever directory JAX
  is already using** (next paragraph), with the min-compile-time /
  min-entry-size floors dropped so even fast compiles persist: jits
  *outside* the wired hot spots also skip the XLA backend compile on
  restart (trace + lower still paid).

**Where JAX's persistent cache lives** is decided in ONE place,
:func:`place_jax_cache`, at ``import mxnet_tpu`` — on every run, not only
under ``MXNET_AOT_CACHE``.  ``JAX_COMPILATION_CACHE_DIR`` set ⇒ JAX reads it
itself and this program sets NO directory in code.  Unset, and the
configured platform is an accelerator ⇒ the fixed ``<checkout>/.jax_cache``
next to this package (the path is part of what keys an entry, so it never
comes from ``tempfile``, a pid or the clock).  Unset on CPU ⇒ no persistent
cache (the donation caution below).  Its hit/miss events are counted under
``tier="xla"`` (:func:`stats` ``xla_hits`` / ``xla_misses``) on every run.

**The CPU-backend donation caution.**  Observed on an earlier XLA:CPU
(reproduced under concurrent process load and bisected against controls;
not re-examined on the installed jax):
an executable *restored from a cache* — either tier — and dispatched with
**donated** arguments intermittently computed a consistently-wrong
trajectory (a small discrete set of wrong results, load-dependent trial to
trial), while freshly compiled executables were bit-exact and stable across
hundreds of trials under the same load.  Serializing every dispatch with
``block_until_ready`` did NOT close it, so this was not a cross-dispatch
overlap race — the restored executable itself mishandled its donation
aliasing.  Non-donated restored executables (the inference path) showed no
deviation under the same protocol.  Two consequences, both encoded here:

* this program never turns JAX's persistent cache on for the CPU platform
  — it restores executables for *every* jit in the process, including
  donated ones this module cannot see (e.g.
  ``gluon.functional.make_train_step``), so on CPU it cannot be made safe
  selectively.  (On TPU, persistent-cache + donated train steps is the
  standard production workflow.)  A user who exports
  ``JAX_COMPILATION_CACHE_DIR`` on CPU has made that choice themselves.
* ``donated=True`` callables skip tier 1's disk entries on the CPU backend
  (in-memory AOT lower/compile split only — a CPU restart re-pays the
  fused-step compile; the serving ladder, non-donated, still restores).
  On TPU-class backends donated entries restore normally, guarded by the
  environment fingerprint.

Accounting: process-local :func:`stats` (always available — the Engine's
``stats()["warmup"]`` block reads it without telemetry) plus
``aot_cache_{hits,misses}_total{tier}`` / ``aot_cache_errors_total{reason}``
in the telemetry registry when ``MXNET_TELEMETRY`` is on, and an
``aot_cache`` attr on the innermost live trace span at prepare time
(docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import bisect
import hashlib
import os
import pickle
import threading

__all__ = ["active", "cache_dir", "activate", "stats", "fingerprint",
           "mesh_descriptor", "CachedFunction"]

_FORMAT = 1  # bump to invalidate every on-disk entry

_mu = threading.Lock()
_stats = {"hits": 0, "misses": 0, "errors": 0,
          "xla_hits": 0, "xla_misses": 0,
          # wall seconds spent in tier-1 XLA compiles this process (ISSUE
          # 20): a warm restart must read 0.0 here on EVERY rank — the
          # train-side analog of the serving warmup's aot_compile_s
          "compile_s": 0.0,
          # JAX's own duration events summed over every program of the
          # process (:func:`_on_jax_duration`): tracing to a jaxpr, lowering
          # it to MLIR, the backend compile (on a persistent-cache hit: the
          # load), and reading the cache entry, a part of the last
          "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
          "cache_load_s": 0.0,
          # the same stages from their time spans (:func:`_on_jax_span`):
          # the seconds covered by at least one span of the stage, so a
          # trace nested in another program's trace counts once
          "trace_union_s": 0.0, "lower_union_s": 0.0, "backend_union_s": 0.0,
          # backend events: a program compiled or loaded from the cache
          "programs": 0,
          # registered-operator calls made while a program was traced
          # (:func:`note_op_traced`)
          "ops_traced": 0}
_DURATION_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_SPAN_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_activated_dir = None
_listener_registered = False


def cache_dir():
    """The ``MXNET_AOT_CACHE`` directory, or None when the cache is off."""
    d = os.environ.get("MXNET_AOT_CACHE", "").strip()
    return d or None


def active():
    return cache_dir() is not None


def max_bytes():
    """``MXNET_AOT_CACHE_MAX_MB`` (default 2048) as bytes; <=0 disables
    eviction."""
    try:
        mb = float(os.environ.get("MXNET_AOT_CACHE_MAX_MB", "2048"))
    except ValueError:
        mb = 2048.0
    return int(mb * 1024 * 1024)


def stats():
    """Process-local event counts.  ``hits``/``misses`` are tier-1 (one
    executable restored from disk / compiled fresh and stored; in-memory
    signature re-use counts as neither); ``xla_hits``/``xla_misses`` mirror
    JAX's persistent-compilation-cache events (tier 2 — every XLA backend
    compile in the process, donated steps included); ``errors`` are
    rejected tier-1 entries (each one a clean miss + recompile).
    ``trace_s`` / ``lower_s`` / ``backend_s`` / ``cache_load_s`` are the
    seconds JAX reports for its compile stages, summed over the process:
    where set-up time goes below ``xla_hits``.  A jit traced inside another
    reports its trace on its own and inside the outer's, so ``trace_s`` is
    an upper estimate for nested programs; ``trace_union_s`` /
    ``lower_union_s`` / ``backend_union_s`` are the seconds that at least
    one span of the stage covers, on any thread, which counts such a
    second once.  ``programs`` counts the backend events (a compile, or a
    load from JAX's persistent cache; an eager operator's one-primitive
    program among them); ``ops_traced`` the registered-operator calls made
    while a program was traced, the same on every run of one program and
    on every host."""
    with _mu:
        return dict(_stats)


def _reset_stats_for_tests():
    with _mu:
        for k in _stats:
            _stats[k] = 0
        for k in _unions:
            _unions[k] = _Union()


class _Union:
    """Length of the union of the intervals added so far, kept as sorted
    disjoint intervals."""

    __slots__ = ("starts", "ends", "total")

    def __init__(self):
        self.starts, self.ends, self.total = [], [], 0.0

    def add(self, a, b):
        i = bisect.bisect_left(self.ends, a)      # first that ends at a or later
        j = bisect.bisect_right(self.starts, b)   # past the last that starts by b
        if i < j:                                 # [i, j) meet [a, b]: merge
            a, b = min(a, self.starts[i]), max(b, self.ends[j - 1])
            self.total -= sum(e - s for s, e in zip(self.starts[i:j],
                                                    self.ends[i:j]))
        self.starts[i:j], self.ends[i:j] = [a], [b]
        self.total += b - a
        return self.total


_unions = {stage: _Union() for stage in _SPAN_STAGES.values()}


def note_op_traced():
    """A registered operator called while a program is traced (no gate:
    trace time only, where the operator's ``named_scope`` opens)."""
    with _mu:
        _stats["ops_traced"] += 1


def _note(kind, reason=None):
    with _mu:
        _stats[kind] += 1
    from . import telemetry

    telemetry.note_aot_cache(kind, reason)
    sp = telemetry.tracing.current()
    if sp is not None:
        sp.set(aot_cache="error:%s" % reason if kind == "errors"
               else kind[:-1])


def _on_jax_event(name, **kw):
    """Tier-2 accounting: forward jax's persistent-compilation-cache events
    into our counters (tier="xla")."""
    from . import telemetry

    if name == "/jax/compilation_cache/cache_hits":
        with _mu:
            _stats["xla_hits"] += 1
        telemetry.note_aot_cache("hits", tier="xla")
    elif name == "/jax/compilation_cache/cache_misses":
        with _mu:
            _stats["xla_misses"] += 1
        telemetry.note_aot_cache("misses", tier="xla")


def _on_jax_duration(name, secs, **kw):
    """Sum jax's compile-stage durations into :func:`stats` (no gate: four
    float additions per compiled program)."""
    key = _DURATION_KEYS.get(name)
    if key is not None:
        with _mu:
            _stats[key] += secs


def _on_jax_span(name, start, end, fun_name="", **kw):
    """Keep each compile stage's union and the backend events in
    :func:`stats` (no gate); while tracing is on, also put the stage into
    the span ring as ``compile.<stage>`` named by its program.  JAX calls
    this when a stage ENDS, so a nested trace arrives before its outer."""
    stage = _SPAN_STAGES.get(name)
    if stage is None:
        return
    with _mu:
        _stats[stage + "_union_s"] = _unions[stage].add(start, end)
        if stage == "backend":
            _stats["programs"] += 1
    from .telemetry import tracing

    tracing.record("compile." + stage, start, end, fun_name=fun_name)


def _exec_dir():
    return os.path.join(cache_dir(), "exec")


def _platform_hint():
    """The platform this process will run on, WITHOUT initializing the jax
    backend.  :func:`place_jax_cache` runs at ``import mxnet_tpu``, which
    must stay legal before ``jax.distributed.initialize()`` / late
    ``jax.config`` updates on multi-host pods — ``jax.default_backend()``
    would latch the backend right there.  Reads the *configured* platform
    list (JAX_PLATFORMS / ``jax_platforms``); when that is unset
    (auto-detect), probes for local TPU chips the way jax itself does (a
    PCI sysfs scan, no backend).  Returns a platform name, or None for
    "unknown"."""
    import jax

    p = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    p = p.split(",")[0].strip().lower()
    if p:
        return p
    from jax._src import hardware_utils

    if hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0:
        return "tpu"
    return None


def place_jax_cache():
    """Decide where JAX's persistent compilation cache lives (module
    docstring), count its hit/miss events and sum jax's compile-stage
    durations and spans.  MUST run before the first
    XLA compile — jax latches the cache directory at first use
    (``mxnet_tpu/__init__.py`` calls this at import) — and must itself not
    trigger backend init, hence :func:`_platform_hint`.  Idempotent."""
    global _listener_registered
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() \
            and not jax.config.jax_compilation_cache_dir \
            and _platform_hint() not in (None, "cpu"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    if not _listener_registered:
        from jax._src import monitoring

        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        monitoring.register_event_time_span_listener(_on_jax_span)
        _listener_registered = True


def activate():
    """Idempotent per-directory ``MXNET_AOT_CACHE`` setup: create
    ``<dir>/exec`` (tier 1) and, when :func:`place_jax_cache` left JAX's
    persistent cache on (tier 2), drop its min-compile-time /
    min-entry-size floors so even fast compiles persist.  Same
    before-first-compile rule as :func:`place_jax_cache`."""
    global _activated_dir
    d = cache_dir()
    if d is None or d == _activated_dir:
        return
    os.makedirs(_exec_dir(), exist_ok=True)
    import jax

    if jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _activated_dir = d


def _cpu_backend():
    import jax

    return jax.default_backend() == "cpu"


def fingerprint(text):
    """Stable short hash of a graph description (e.g. ``Symbol.tojson()``)."""
    if isinstance(text, str):
        text = text.encode("utf-8")
    return hashlib.sha256(text).hexdigest()[:16]


def symbol_fingerprint(symbol):
    """Cached :func:`fingerprint` of a Symbol's json — computed once per
    Symbol object (the serving proto shares one Symbol across all buckets)."""
    fp = getattr(symbol, "_aot_fingerprint", None)
    if fp is None:
        fp = fingerprint(symbol.tojson())
        try:
            symbol._aot_fingerprint = fp
        except Exception:
            pass
    return fp


def _versions():
    """(jax, jaxlib) version strings — separate so tests can stub a stale
    build and assert the clean-miss path."""
    import jax
    import jaxlib

    return (jax.__version__, jaxlib.__version__)


def mesh_descriptor(mesh):
    """Canonical, comparable description of a ``jax.sharding.Mesh`` (or
    None): axis names + sizes + device kind layout.  Part of the verified
    environment fingerprint, NOT the file name — a restart onto a different
    topology must read the old entry, miss cleanly, and overwrite it."""
    if mesh is None:
        return None
    return {"axes": [str(a) for a in mesh.axis_names],
            "shape": [int(mesh.devices.shape[i])
                      for i in range(mesh.devices.ndim)]}


def _numerics_contract():
    """``analysis.numerics.contract_fingerprint()`` — version identity of
    the cast-plan contract (ISSUE 11), imported lazily so the cache layer
    never pays the analysis package on processes that import neither."""
    from .analysis import numerics

    return numerics.contract_fingerprint()


def _env_fingerprint(mesh_desc=None):
    import jax

    from . import graph_passes

    jv, jlv = _versions()
    devs = jax.devices()
    # "passes": the graph-pass pipeline (ISSUE 7) that shaped every plan
    # compiled in this configuration — None with MXNET_GRAPH_PASSES=0.
    # Verified (not just keyed) so an executable persisted under a
    # different pass configuration, or by a build whose pass versions
    # changed, can never be restored: it misses cleanly and is recompiled.
    fp = {"format": _FORMAT, "jax": jv, "jaxlib": jlv,
          "backend": jax.default_backend(),
          "device_kind": str(devs[0].device_kind), "n_devices": len(devs),
          # process topology (ISSUE 20): an executable compiled for an
          # N-process pod encodes cross-host collectives — restoring it in
          # a job with a different process count (or as the wrong rank
          # count after an elastic resize) must miss cleanly.  Every rank
          # of the SAME topology fingerprints identically, which is what
          # makes N-process warm restarts warm on every rank.
          "n_processes": jax.process_count(),
          "mesh": mesh_desc,
          "passes": graph_passes.pipeline_fingerprint(),
          # "numerics" (ISSUE 11): the cast-plan contract versions
          # (sensitivity registry + numerics analyzer).  A given plan's
          # CastPlan fingerprint moves only when the plan moves (already
          # keyed via symbol + pass fingerprints) or when these versions
          # bump — so verifying the versions here is exactly "fold the
          # cast-plan fingerprint into the key path": once the bf16 pass
          # rewrites plans from CastPlans, an executable built under an
          # older numerics contract misses cleanly instead of restoring
          # stale numerics.
          "numerics": _numerics_contract()}
    # "autotune" (ISSUE 9): adopted winners shape traced programs (the
    # dconv block grid reads the store at trace time), so the store state
    # digest joins the verified fingerprint while the gate is on — a
    # re-search that changes winners, or toggling MXNET_AUTOTUNE, is a
    # clean miss in BOTH directions.  Key absent with the gate off keeps
    # pre-autotune fingerprints (and their cached executables) byte-
    # identical, per the off-path contract.
    from .base import env_flag

    if env_flag("MXNET_AUTOTUNE"):
        from .autotune import store as _at_store

        fp["autotune"] = _at_store.state_digest()
    return fp


def _evict():
    """Drop oldest-mtime entries until the exec dir fits the size budget.
    Per-entry best-effort: a concurrent writer in a SHARED cache dir may
    delete/rename files between listdir and stat, and one vanished file must
    not abort the pass (the budget would silently stop being enforced).
    In-flight ``*.tmp.<pid>`` spool files are not candidates — evicting one
    would break that writer's atomic rename."""
    cap = max_bytes()
    if cap <= 0:
        return
    try:
        names = os.listdir(_exec_dir())
    except OSError:
        return
    entries = []
    for fn in names:
        if not fn.endswith(".jx"):
            continue
        p = os.path.join(_exec_dir(), fn)
        try:
            st = os.stat(p)
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, p))
    total = sum(e[1] for e in entries)
    for mtime, size, p in sorted(entries):
        if total <= cap:
            break
        try:
            os.remove(p)
        except OSError:
            continue  # undeletable entry still occupies budget
        total -= size


class CachedFunction:
    """Drop-in wrapper for a jitted callable with a per-signature AOT
    executable cache persisted to disk.

    ``key_parts`` is the logical identity of the computation — graph
    fingerprint, differentiated/constant name split, optimizer kind + folded
    hyperparams, donation layout, gate flags — everything that changes the
    compiled program *other than* argument shapes/dtypes (those enter the
    key from the arguments at prepare time) and the environment (verified
    inside the entry, see :func:`_env_fingerprint`).

    The three-stage surface mirrors ``jax.stages``:

    * :meth:`lower_prepare` — disk probe, then (on miss) trace + lower.
      Pure host work: safe to run concurrently for many signatures and off
      the serving device loop.
    * :meth:`finalize` — XLA backend compile of a lowered handle + store.
      The expensive, serialized stage.
    * :meth:`__call__` — dispatch through the prepared executable,
      preparing on demand; degrades to the wrapped jit on any executable
      error (counted), so a cache problem can slow a request but never
      fail it.

    ``donated=True`` declares that the wrapped jit donates inputs: the disk
    tier is then disabled on the CPU backend, where restored donated
    executables compute intermittently-wrong trajectories (the donation
    hazard, module docstring).  ``persist=False`` disables the disk tier on
    every backend (in-memory AOT split only).

    ``passes_on`` pins whether the wrapped computation was lowered through
    the graph-pass pipeline (ISSUE 7): when true, the pipeline's
    (name, version) fingerprint joins the logical key, so pass-optimized
    and raw plans can never share an entry.  Callers that snapshot the
    ``MXNET_GRAPH_PASSES`` gate (Executor, FusedStepper) pass their
    snapshot; the default (None) reads the gate live.  With the gate off
    nothing is appended — keys stay byte-identical to pre-pass builds."""

    def __init__(self, jit_fn, key_parts, name="fn", mesh_desc=None,
                 persist=True, donated=False, passes_on=None):
        activate()
        self._jit = jit_fn
        self._name = str(name)
        key_parts = tuple(key_parts)
        from . import graph_passes

        if passes_on is None:
            passes_on = graph_passes.enabled()
        if passes_on:
            key_parts += (("graph_passes",
                           "|".join("%s:%d" % nv
                                    for nv in graph_passes.pipeline())),)
        self._key = repr(key_parts)
        self._mesh_desc = mesh_desc
        self._donated = bool(donated)
        self._persist = bool(persist) and not (self._donated and
                                               _cpu_backend())
        self._exes = {}
        self._lock = threading.Lock()
        self.__wrapped__ = jit_fn

    # instrument_step's compile-vs-steady-state detector reads this; a disk
    # restore grows it too (an executable was installed either way)
    def _cache_size(self):
        return len(self._exes)

    @staticmethod
    def _sig(args):
        """In-memory signature key: (treedef, ((shape, dtype), ...)).  The
        treedef OBJECT is the key component — hashable, and much cheaper
        than stringifying the whole tree, since this runs per dispatch on
        the hot path (every fused step / served batch).  :meth:`_sig_str`
        canonicalizes for the disk paths only."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef,
                tuple((tuple(getattr(v, "shape", ())),
                       str(getattr(v, "dtype", type(v).__name__)))
                      for v in leaves))

    @staticmethod
    def _sig_str(sig):
        """Cross-process-stable string form of a signature, for the entry
        file name and the verified payload key (treedefs render
        structurally, so equal trees stringify equally in any process)."""
        return repr((str(sig[0]), sig[1]))

    def _path(self, sig):
        h = hashlib.sha256(
            repr((self._name, self._key,
                  self._sig_str(sig))).encode("utf-8")).hexdigest()
        return os.path.join(_exec_dir(), "%s-%s.jx" % (self._name, h[:32]))

    def _try_load(self, sig):
        """Deserialize one entry → ``(executable, cost_fingerprint)``, or
        None on ANY problem (mismatched key or environment →
        ``key_mismatch``; truncated/corrupt/unreadable → ``deserialize``)
        — the cache must never turn into a crash.  ``cost_fingerprint``
        is the flops/bytes identity captured when the entry was stored
        (None for entries written before it existed)."""
        path = self._path(sig)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
            if (payload.get("key") != self._key
                    or payload.get("sig") != self._sig_str(sig)
                    or payload.get("env") != _env_fingerprint(self._mesh_desc)):
                _note("errors", "key_mismatch")
                return None
            from jax.experimental import serialize_executable

            # on the devices it was compiled for: the loader's default is
            # every device of the backend, and a one-device executable
            # loaded over eight refuses its arguments at dispatch
            devices = payload.get("devices")
            if devices is not None:
                import jax

                by_id = {d.id: d for d in jax.devices()}
                devices = [by_id[i] for i in devices]
            exe = serialize_executable.deserialize_and_load(
                payload["blob"], payload["in_tree"], payload["out_tree"],
                execution_devices=devices)
            os.utime(path, None)  # LRU signal for _evict
            return exe, payload.get("cost")
        except Exception:
            _note("errors", "deserialize")
            return None

    def _store(self, sig, compiled):
        """Persist one compiled executable (atomic rename so a crashed
        writer can only ever leave a *missing* entry, not a torn one).
        Best-effort: a backend whose executables don't serialize (counted)
        still runs from the in-memory cache."""
        try:
            from jax.experimental import serialize_executable

            from .telemetry import costplane

            blob, in_tree, out_tree = serialize_executable.serialize(compiled)
            payload = {"key": self._key, "sig": self._sig_str(sig),
                       "env": _env_fingerprint(self._mesh_desc),
                       "blob": blob, "in_tree": in_tree,
                       "out_tree": out_tree,
                       "devices": [d.id for d in compiled.
                                   runtime_executable().local_devices()],
                       # cost identity of the program as compiled (ISSUE
                       # 20): a restore records a ledger row from this, so
                       # a warm pod restart still proves every rank runs
                       # the identical program via the cross-rank
                       # ledger-divergence diff
                       "cost": costplane.cost_fingerprint(compiled)}
            path = self._path(sig)
            tmp = "%s.tmp.%d" % (path, os.getpid())
            with open(tmp, "wb") as f:
                pickle.dump(payload, f)
            os.replace(tmp, path)
            _evict()
        except Exception:
            _note("errors", "serialize")

    def lower_prepare(self, *args):
        """Stage 1: → handle dict.  ``source`` is ``"cached"`` (signature
        already prepared in this process), ``"disk"`` (restored — counted as
        a hit; no compile left to pay), or ``"lower"`` (traced + lowered
        here; :meth:`finalize` owes the compile).  ``lower_s`` is the disk
        restore or trace+lower wall time."""
        import time

        sig = self._sig(args)
        with self._lock:
            if sig in self._exes:
                return {"sig": sig, "source": "cached", "lower_s": 0.0}
        t0 = time.perf_counter()
        loaded = self._try_load(sig) if self._persist else None
        if loaded is not None:
            exe, cost = loaded
            with self._lock:
                self._exes[sig] = exe
            _note("hits")
            from .telemetry import costplane

            # warm-restart ledger row (ISSUE 20): compile_s 0.0, cost
            # identity carried from the entry — the pod divergence
            # detector can still diff this rank against the fleet
            costplane.record_restore(self._name, self._key,
                                     self._sig_str(sig), cost)
            return {"sig": sig, "source": "disk",
                    "lower_s": time.perf_counter() - t0}
        from .telemetry import costplane

        # compile plane (ISSUE 13): bracket the trace with a Pallas cost-
        # registry snapshot so finalize can attribute declared kernel costs
        # to THIS executable's row.  Warmup lowers many buckets in a thread
        # pool — overlapping brackets mark each other dirty and their
        # declared/drift surfaces degrade to empty rather than attributing
        # another executable's kernels.  Gate off = one env read, no token.
        tc0 = costplane.open_trace_bracket()
        t0 = time.perf_counter()
        try:
            lowered = self._jit.lower(*args)
        finally:
            costplane.close_trace_bracket(tc0)
        return {"sig": sig, "source": "lower", "lowered": lowered,
                "lower_s": time.perf_counter() - t0, "tc0": tc0}

    def finalize(self, handle):
        """Stage 2: compile a ``"lower"`` handle (and persist it — counted
        as a miss); a ``"cached"``/``"disk"`` handle passes through with
        ``compile_s`` 0."""
        import time

        if handle["source"] != "lower":
            return dict(handle, compile_s=0.0)
        t0 = time.perf_counter()
        compiled = handle["lowered"].compile()
        compile_s = time.perf_counter() - t0
        with _mu:
            _stats["compile_s"] += compile_s
        from .telemetry import costplane

        if costplane.enabled():
            # compile plane (ISSUE 13): one ledger row per executable XLA
            # built here — disk restores record nothing (XLA built nothing)
            costplane.record_compile(self._name, self._key,
                                     self._sig_str(handle["sig"]), compiled,
                                     compile_s, tc0=handle.get("tc0"))
        with self._lock:
            self._exes[handle["sig"]] = compiled
        if self._persist:
            _note("misses")
            self._store(handle["sig"], compiled)
        return {"sig": handle["sig"], "source": "compile",
                "lower_s": handle["lower_s"], "compile_s": compile_s}

    def prepare(self, *args):
        """lower_prepare + finalize in one call → the finalize row."""
        return self.finalize(self.lower_prepare(*args))

    def __call__(self, *args):
        sig = self._sig(args)
        exe = self._exes.get(sig)
        if exe is None:
            self.prepare(*args)
            exe = self._exes.get(sig)
        if exe is None:  # compile failed upstream; let jit raise its error
            return self._jit(*args)
        try:
            return exe(*args)
        except Exception:
            # a deserialized executable the runtime won't take (e.g. device
            # set changed under us): drop it and degrade to the jit path —
            # slower, never wrong.  NOT with donated args: the failed
            # executable may already have consumed (aliased/deleted) its
            # donated buffers, and re-invoking the jit on deleted arrays
            # would swallow the real error under a confusing second one.
            _note("errors", "dispatch")
            with self._lock:
                self._exes.pop(sig, None)
            if self._donated:
                raise
            return self._jit(*args)
