"""Host time a step spends in ``fused.commit``, median over the window's steps,
in ms: FusedStepper.run after the call has returned: rebinding parameters,
gradients, statistics and optimizer state, wrapping the outputs.  A child
span of the program's ``update`` (mxnet_tpu/module/fused_step.py).  Source:
program span."""
from benchmark import program_spans


def read(run):
    return program_spans.median_ms("fused.commit")
