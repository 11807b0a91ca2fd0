"""Keys the sparse attention read over the keys it could have read, in %:
the step's device counters ``selected_keys`` / ``causal_keys`` (summed over
layers by the program, recorded on its ``step`` span with ``tracing.count``),
sum over the window's steps over sum.  23.4 by the closed form at 16384
tokens and top-2048 (benchmark/work/keye_lm.py ``selected_share``); 100 if
the selection is bypassed.  Nothing to read where the program records no
such counters.  Source: program counter."""
from benchmark import program_spans


def read(run):
    roots = [s for g in program_spans.by_root("step").values() for s in g
             if s["parent"] is None]
    causal = sum(s["attrs"].get("causal_keys", 0) for s in roots)
    if not causal:
        return None
    return sum(s["attrs"].get("selected_keys", 0) for s in roots) \
        / causal * 100.0
