"""The program's own spans of the traced window, for the per-layer metrics
that read them (source ``program_span`` / ``program_counter``).

``mxnet_tpu.telemetry.tracing`` records a span while a ``jax.profiler``
session is live, which in a ``--trace 1`` run is exactly the window; the
readers run in the same process afterwards and read its ring.  A program
that has no such spans (a parent commit, a cell that bypasses ``Module.fit``,
``--trace 0``) gives an empty list, and every reader then returns ``None``.
"""
import statistics


def spans():
    """Finished spans as dicts (name, trace, span, parent, start_us, dur_us,
    attrs), oldest first; [] where the program records none."""
    from mxnet_tpu.telemetry import tracing

    snapshot = getattr(tracing, "snapshot", None)
    return snapshot() if snapshot is not None else []


def median_ms(name):
    """Median duration in ms of the window's spans called ``name``."""
    durs = [s["dur_us"] for s in spans() if s["name"] == name]
    return statistics.median(durs) / 1e3 if durs else None


def by_root(root_name):
    """{trace id: every span of that trace} for the traces whose root is
    called ``root_name`` and has finished."""
    groups = {}
    for s in spans():
        groups.setdefault(s["trace"], []).append(s)
    return {t: g for t, g in groups.items()
            if any(s["parent"] is None and s["name"] == root_name for s in g)}
