"""Share of the (query, key) pairs in the attention blocks that HSTU's
kernel visits that are causal pairs of valid events: the configuration's
``pairs_useful`` over ``pairs_computed``, summed over the window."""


def read(w):
    c = w.counters or {}
    if not c.get("pairs_computed"):
        return None
    return c["pairs_useful"] / c["pairs_computed"] * 100.0
