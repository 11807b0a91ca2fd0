"""The fullest held expert's (token, expert) pairs over the mean held
expert's, per layer, averaged over layers and the window's steps: the
step's device counters ``expert_pairs_max`` (sum over layers of the fullest
expert's pairs) x experts held / ``expert_pairs`` (all held pairs), recorded
on the program's ``step`` span.  1 is an even load; the buffer of held pairs
(``moe_capacity_factor``) has to cover what this reads.  Nothing to read
where the program records no such counters.  Source: program counter."""
from benchmark import program_spans


def read(run):
    roots = [s for g in program_spans.by_root("step").values() for s in g
             if s["parent"] is None]
    pairs = sum(s["attrs"].get("expert_pairs", 0) for s in roots)
    if not pairs:
        return None
    return sum(s["attrs"].get("expert_pairs_max", 0) for s in roots) \
        * run.config["num_experts"] / pairs
