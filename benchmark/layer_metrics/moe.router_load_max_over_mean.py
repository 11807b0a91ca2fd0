"""The fullest routed expert's (token, expert) pairs over the mean expert's,
over ALL the experts of a layer (held here or not), per expert layer, averaged
over layers and the window's steps: the step's device counters
``router_pairs_max`` (sum over the expert layers of the fullest expert's
pairs) x the router's width / ``router_pairs`` (all pairs), recorded on the
program's ``step`` span.  1 is an even load: what the selection bias's update
drives towards over a window.  The router's width is the published count of
routed experts where the configuration is a share.  Nothing to read where
the program records no such counters.  Source: program counter."""
from benchmark import program_spans


def read(run):
    roots = [s for g in program_spans.by_root("step").values() for s in g
             if s["parent"] is None]
    pairs = sum(s["attrs"].get("router_pairs", 0) for s in roots)
    if not pairs:
        return None
    cfg = run.config
    width = cfg.get("deployment", {}).get("published", cfg)["n_routed_experts"]
    return sum(s["attrs"].get("router_pairs_max", 0) for s in roots) \
        * width / pairs
