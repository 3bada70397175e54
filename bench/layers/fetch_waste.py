"""Fetch waste (%): the share of the bytes gathered home that no ticket
keeps, 1 - (sum of ``FlushRecord.answer_bytes``) / (sum of
``FlushRecord.fetched_bytes``) over the window's flushes: the padded
batch's filler slots and each slot's bucket padding.  Source: the
server's per-flush byte counts (program counters); nothing where the
server keeps no such fields."""


def read(ctx):
    flushes = ctx["records"]["flushes"]
    fetched = [getattr(f, "fetched_bytes", None) for f in flushes]
    kept = [getattr(f, "answer_bytes", None) for f in flushes]
    if not flushes or None in fetched or None in kept or sum(fetched) <= 0:
        return None
    return 100.0 * (1.0 - sum(kept) / sum(fetched))
