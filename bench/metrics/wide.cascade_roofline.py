"""The wide datapath's cascade (``core/wide.py``: digit-split NTT,
pointwise product, inverse NTT) share of its HBM roofline: the
compulsory bytes of the dispatches in the traced stretch
(``bench/work.py``) at the chip's peak bandwidth, over the device time
of the operations under ``parentt.cascade``.  A program whose wide
branch opens no stage scopes leaves nothing to read."""
from bench import work


def read(records):
    tr = records["trace"]
    seconds = tr.scope_s.get("cascade", 0.0)
    if not seconds or not tr.modules:
        return None
    per_dispatch = work.config_bytes(records["config"], records["batch_slots"])["cascade"]
    bw = work.peaks_for(records["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_dispatch * tr.modules / bw / seconds
