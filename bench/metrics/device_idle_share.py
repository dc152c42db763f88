"""Share of the traced window in which no operation ran on the chip, in
per cent, averaged over the cell's chips (``devtrace.reduce``)."""


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    ids = sorted(tr["devices"], key=int)[:rec["chips"]]
    return 100.0 * sum(tr["devices"][d]["idle_share"] for d in ids) / len(ids)
