"""Host time a step spends in ``fused.prepare``, median over the window's steps,
in ms: everything FusedStepper.run does before it launches the step (operand
lists, optimizer-state checks, update counts, the per-parameter lr / wd
vectors, the key).  A child span of the program's ``update``
(mxnet_tpu/module/fused_step.py).  Source: program span."""
from benchmark import program_spans


def read(run):
    return program_spans.median_ms("fused.prepare")
