"""Share of the steps' host time spent waiting for the next batch, in %:
the window's ``data_wait`` spans (the iterator's ``next`` and
``Module.prepare``) over its ``step`` spans.  Source: program span."""
from benchmark import program_spans


def read(run):
    total = {"step": 0.0, "data_wait": 0.0}
    for s in program_spans.spans():
        if s["name"] in total:
            total[s["name"]] += s["dur_us"]
    return total["data_wait"] / total["step"] * 100.0 if total["step"] else None
