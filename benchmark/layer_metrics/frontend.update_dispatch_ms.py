"""Host time a step spends in ``fused.dispatch``, median over the window's
steps, in ms: the one jitted call of FusedStepper.run, from the call until it
returns (argument handling of several hundred arrays and the launch; not the
device's run).  A child span of the program's ``update``
(mxnet_tpu/module/fused_step.py).  Source: program span."""
from benchmark import program_spans


def read(run):
    return program_spans.median_ms("fused.dispatch")
