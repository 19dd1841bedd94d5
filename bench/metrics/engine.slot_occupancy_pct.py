"""Share of the engine's batch slots that carried a request over the
window: served / (dispatches x batch_slots), from the engine's counters."""


def read(records):
    c = records["counters"]
    if not c["dispatches"]:
        return None
    return 100.0 * c["served"] / (c["dispatches"] * records["batch_slots"])
