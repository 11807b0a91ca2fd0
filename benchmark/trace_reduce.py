"""Reduction of a ``jax.profiler`` trace (xplane.pb) to what the per-layer
metrics read.  Nothing here knows a cell, a model or a metric's name.

What a TPU trace holds (looked at by hand, PERF.md section 6): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
executed program) and ``XLA Ops`` (one event per HLO instruction, named by
the instruction's own text: ``%name = type opcode(operands), attrs``; a
Pallas kernel is a ``custom-call`` whose instruction name is the kernel's
``name=``).  The host is the plane ``/host:CPU``; ``TraceAnnotation`` spans
are events of its ``python`` line.  Times are nanoseconds on one clock.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def parse_op(text):
    """``%name = type opcode(...), attrs`` -> (name, opcode).  An event that
    is not an instruction's text is its own name, with no opcode."""
    if " = " not in text:
        return text.lstrip("%"), ""
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    return name.lstrip("%"), (m.group(1) if m else "")


def is_collective(opcode):
    return any(opcode.startswith(c) for c in COLLECTIVES)


def is_convolution(name, opcode, text):
    """A convolution, or a fusion whose root is one: the TPU compiler names
    those ``convolution...fusion`` or marks them ``kind=kOutput`` (matrix
    products are convolutions to it, so dense layers count here too)."""
    return opcode == "convolution" or "convolution" in name \
        or (opcode == "fusion" and "kind=kOutput" in text)


def is_formatting(name, opcode):
    """Copies, transposes and layout changes, alone or as a fusion."""
    if opcode in ("copy", "transpose", "bitcast-convert", "reshape"):
        return True
    return opcode == "fusion" and any(
        w in name for w in ("copy", "transpose", "bitcast"))


def union_length(intervals):
    """Total length covered by [(start, end)], overlaps counted once."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def subtract_length(a, b):
    """Length of the part of intervals ``a`` that no interval of ``b``
    covers."""
    b = merge(b)
    total = 0.0
    j = 0
    for s, e in merge(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(intervals):
    """[(start, end)] of the idle stretches between merged intervals."""
    m = merge(intervals)
    return [(m[i][1], m[i + 1][0]) for i in range(len(m) - 1)]


class Device:
    """One chip's part of a trace."""

    def __init__(self, ordinal):
        self.ordinal = ordinal
        self.ops = []        # (start_s, end_s, name, opcode, text)
        self.modules = []    # (start_s, end_s, name)

    def leaf_ops(self):
        return [o for o in self.ops if o[3] not in CONTAINERS]

    def busy_s(self):
        return union_length([(o[0], o[1]) for o in self.ops])

    def time_where(self, pred):
        return sum(o[1] - o[0] for o in self.leaf_ops() if pred(o))

    def main_module(self):
        """Name of the program that took most of the device's time: the
        training step."""
        total = {}
        for s, e, n in self.modules:
            total[n] = total.get(n, 0.0) + e - s
        return max(total, key=total.get) if total else None

    def steps(self):
        main = self.main_module()
        return sorted((s, e) for s, e, n in self.modules if n == main)

    def exposed_collective_s(self):
        coll = [(o[0], o[1]) for o in self.leaf_ops() if is_collective(o[3])]
        rest = [(o[0], o[1]) for o in self.leaf_ops() if not is_collective(o[3])]
        return subtract_length(coll, rest)


class Trace:
    def __init__(self, devices, host_spans):
        self.devices = devices          # [Device], by ordinal
        self.host_spans = host_spans    # (start_s, end_s, name)

    def fullest(self):
        """The device that was busy longest: per-step times are read on it."""
        return max(self.devices, key=lambda d: d.busy_s())

    def top_ops(self, n=10):
        """[[name, seconds]] of the fullest device, instructions of one name
        added up."""
        dev = self.fullest()
        total = {}
        for s, e, name, opcode, _ in dev.leaf_ops():
            key = "%s:%s" % (opcode or "event", name)
            total[key] = total.get(key, 0.0) + e - s
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k[:96], v] for k, v in top]

    def idle_by_span(self, spans, n=10):
        """[[span, seconds]]: every idle stretch of the fullest device, given
        to the host span (of those named) that covers most of it."""
        dev = self.fullest()
        named = [h for h in self.host_spans if h[2] in spans]
        total = {}
        for s, e in gaps([(o[0], o[1]) for o in dev.ops]):
            best, cover = "_no_host_span_", 0.0
            for hs, he, name in named:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            total[best] = total.get(best, 0.0) + e - s
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path, span_names=()):
    """``path``: an xplane.pb -> Trace.  Raises where the trace has no TPU
    plane with operations: a CPU trace is no device trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    keep = set(span_names)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        name, opcode = parse_op(ev.name)
                        dev.ops.append((s, s + ev.duration_ns * 1e-9, name,
                                        opcode, ev.name))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev.modules.append((s, s + ev.duration_ns * 1e-9,
                                            ev.name.split("(")[0]))
            if dev.ops:
                devices.append(dev)
        elif plane.name == HOST_PLANE and keep:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    if not devices:
        raise ValueError("%s holds no TPU plane with operations" % path)
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, spans)
