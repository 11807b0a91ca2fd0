"""Programs the host launches on the device per training step, median over
the window's steps: the ``dispatch`` counts (``tracing.count`` at the fused
step's launch, the executor's, the legacy optimizer's, and every eager
operator) summed over the spans of one ``step`` root.  Source: program
counter."""
import statistics

from benchmark import program_spans


def read(run):
    steps = program_spans.by_root("step")
    if not steps:
        return None
    return statistics.median(
        sum(s["attrs"].get("dispatch", 0) for s in group)
        for group in steps.values())
