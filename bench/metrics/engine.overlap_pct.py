"""Share of the window's dispatches that the engine began while another
of its dispatches was outstanding, in %: the ``engine.step`` spans that
closed ``ok`` with ``overlapped`` true, over all that closed ``ok``.  A
program whose steps carry no ``overlapped`` attribute leaves nothing to
read."""


def read(records):
    steps = [s["attrs"] for s in records["spans"]
             if s["name"] == "engine.step" and s["status"] == "ok"]
    if not any("overlapped" in a for a in steps):
        return None
    return 100.0 * sum(bool(a.get("overlapped")) for a in steps) / len(steps)
