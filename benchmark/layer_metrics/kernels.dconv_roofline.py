"""Share of its roofline that the deformable-im2col Pallas kernel pair
reaches, in %: the least time the chip could take for the operator's own
work (``dconv_min_seconds`` of the configuration's counter under
benchmark/work/: bytes-bound) over the traced time of the events named
``dconv_col_pallas_fwd`` / ``_bwd``.  Nothing to read where no such kernel
ran or the architecture counts none.  Source: device trace."""

KERNELS = ("dconv_col_pallas_fwd", "dconv_col_pallas_bwd")


def read(run):
    dev = run.trace.fullest()
    t = dev.time_where(lambda o: o[3] == "custom-call"
                       and any(k in o[2] for k in KERNELS))
    least_of = getattr(run.work.counter(run.config), "dconv_min_seconds", None)
    if t <= 0 or not run.steps or least_of is None:
        return None
    least, _ = least_of(
        run.config, run.items_per_step // run.chips, run.peaks)
    return least * run.steps / t * 100.0
