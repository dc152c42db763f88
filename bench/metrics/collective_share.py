"""Share of the chips' busy time spent in collectives (all-reduce and the
like, by XLA operation name), in per cent: the sharded plane's psums
(``fed/execplan.py``)."""


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    ids = sorted(tr["devices"], key=int)[:rec["chips"]]
    busy = sum(tr["devices"][d]["busy_s"] for d in ids)
    if busy <= 0:
        return None
    return 100.0 * sum(tr["devices"][d]["collective_s"] for d in ids) / busy
