"""The whole step's share of the chips' peak while the chips are busy, in
%: model FLOPs of the window's steps (forward + backward from the
configuration's shapes, benchmark/work/) over the seconds in which an
operation ran on the device (the trace's busy time, mean over the chips) x
chips x bf16 peak.  Host stalls between programs are not in it: they are
``device.idle_share``, and the two multiply to the window's share of the
peak.  The fp32 ResNet cell is held against the bf16 peak too: it is the
MXU's, and the fastest this chip multiplies.  Source: device trace."""


def read(run):
    busy = sum(d.busy_s() for d in run.trace.devices) / len(run.trace.devices)
    if not run.steps or busy <= 0:
        return None
    flops = run.work.train_flops_per_item(run.config) \
        * run.items_per_step * run.steps
    return flops / (busy * run.chips * run.peaks["bf16_flops_per_s"]) * 100.0
