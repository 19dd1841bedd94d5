"""The wide datapath's CRT stages (``parentt.decompose`` and
``parentt.compose``: the paper's pre- and post-processing in 45-bit
digit-split arithmetic, the inverse CRT in 14-bit limbs repacked to
28) share of their HBM roofline: the compulsory bytes of the
dispatches in the traced stretch (``bench/work.py``) at the chip's
peak bandwidth, over the device time of the operations under the two
scopes.  A program whose wide branch opens no stage scopes leaves
nothing to read."""
from bench import work


def read(records):
    tr = records["trace"]
    seconds = tr.scope_s.get("decompose", 0.0) + tr.scope_s.get("compose", 0.0)
    if not seconds or not tr.modules:
        return None
    per_dispatch = work.config_bytes(records["config"], records["batch_slots"])["crt"]
    bw = work.peaks_for(records["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_dispatch * tr.modules / bw / seconds
