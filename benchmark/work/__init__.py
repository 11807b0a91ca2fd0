"""Operations and bytes that the configurations' mathematics needs, from
their shapes alone.

Never from ``compiled.cost_analysis()`` nor from the program's cost registry
(``ops/pallas_kernels.py cost_dconv_col_*``): both count what today's
implementation does (one-hot matrix products), and would move with it.  A
roofline or MFU share has to be of the operator's own work.

A configuration names its counter under ``"work"``: the file
``benchmark/work/<name>.py`` with ``layers(cfg)`` (every convolution and
dense layer of one image: name, forward MACs, whether a backward pass goes
through it) and, where the architecture has a kernel with a roofline, that
kernel's least time.  A later PR adds an architecture as a file here.
"""
import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks(device_kind):
    """The chip's published peaks.  An unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in benchmark/peaks.json "
                       "(known: %s)" % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]


def counter(cfg):
    """The configuration's counter: ``benchmark/work/<cfg["work"]>.py``."""
    return importlib.import_module("benchmark.work." + cfg["work"])


def conv(name, hw, cout, cin, k, trained=True):
    return {"name": name, "macs": hw[0] * hw[1] * cout * cin * k * k,
            "trained": trained}


def forward_macs(cfg):
    return sum(l["macs"] for l in counter(cfg).layers(cfg))


def train_flops_per_item(cfg):
    """Forward + backward of one image: 2 FLOPs a MAC, the backward pass
    twice the forward (one product for the input's gradient, one for the
    weight's), and no backward where none is needed.  Recomputation does
    not count."""
    return sum(2 * l["macs"] * (3 if l["trained"] else 1)
               for l in counter(cfg).layers(cfg))
