"""Companion script for docs/tutorials/profiler.md (reference
``docs/tutorials/python/profiler.md`` + ``example/profiler/``): configure
the profiler, bracket a workload, dump a chrome-trace JSON, and inspect
per-tensor stats with Monitor."""
from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler

tmp = tempfile.mkdtemp()
trace = os.path.join(tmp, "profile.json")

# --- 1. configure + bracket a workload -----------------------------------
profiler.set_config(profile_all=True, filename=trace)
profiler.set_state("run")

a = nd.random.uniform(shape=(256, 256))
b = nd.random.uniform(shape=(256, 256))
c = nd.dot(a, b)
d = nd.relu(c) + 1.0
d.wait_to_read()

# user-code annotation: domains + tasks (reference profiler.py:151-240)
domain = profiler.Domain("my_app")
task = profiler.Task(domain, "postprocess")
task.start()
e = (d * 2).sum()
e.wait_to_read()
task.stop()

# counters (reference ProfileCounter)
counter = profiler.Counter(domain, "batches_done")
counter.set_value(1)
counter += 1

profiler.set_state("stop")
profiler.dump()

# --- 2. the dump is chrome://tracing JSON --------------------------------
with open(trace) as f:
    events = json.load(f)["traceEvents"]
names = {ev.get("name") for ev in events}
assert any("dot" in (n or "").lower() for n in names), sorted(names)[:20]
assert "postprocess" in names, sorted(names)[:20]
print("chrome trace: %d events incl. op events and the 'postprocess' task"
      % len(events))

# --- 3. dumps() returns the same JSON as a string.  dump(finished=True)
# drained the samples above; what is left is metadata (ph "M": the clock
# anchor for tools/trace_merge.py and the domain's process name), which
# every dump carries -------------------------------------------------------
left = json.loads(profiler.dumps())["traceEvents"]
assert [ev for ev in left if ev["ph"] != "M"] == [], left

# --- 4. Monitor: per-tensor stats through an executor --------------------
x = mx.sym.Variable("x")
h = mx.sym.FullyConnected(x, num_hidden=8, name="fc")
out = mx.sym.SoftmaxOutput(h, name="sm")
exe = out.simple_bind(x=(4, 16), sm_label=(4,))
seen = []
mon = mx.monitor.Monitor(1, stat_func=lambda arr: nd.max(nd.abs(arr)),
                         pattern=".*fc.*")
mon.install(exe)
exe.arg_dict["x"][:] = np.random.RandomState(0).rand(4, 16)
mon.tic()
exe.forward(is_train=True)
for batch, name, val in mon.toc():
    seen.append(name)
assert any("fc" in n for n in seen), seen
print("Monitor captured per-tensor stats: %s" % seen[:4])

print("PROFILER TUTORIAL OK")
