"""The pass after which a token leaves the loop, in the mean over the window's
tokens: ``sum_t t x p_t`` with ``p_t`` the share of a token's mass that the
exit gate lets go after pass t (the last pass takes what is left).  From the
step's device counters on the program's ``step`` span: ``exit_step_milli``
(the rounded sum over a step's tokens of 1000 x that sum) over
``gate_tokens``, summed over the window's steps.  1 is a gate that always
leaves at once, the number of passes one that never does, 1.875 an undecided
one (every gate a half) over four passes.  What training pays for the later
passes does not depend on it; what serving would pay does.  Nothing to read
where the program records no such counters.  Source: program counter."""
from benchmark import program_spans


def read(run):
    roots = [s for g in program_spans.by_root("step").values() for s in g
             if s["parent"] is None]
    tokens = sum(s["attrs"].get("gate_tokens", 0) for s in roots)
    if not tokens:
        return None
    return sum(s["attrs"].get("exit_step_milli", 0) for s in roots) \
        / tokens / 1000.0
