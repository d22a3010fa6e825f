"""Share of the routed (token, k) pairs that the expert layer's largest
dispatch buffer has rows for: ``100 · buffer_rows / pairs`` (pairs =
tokens · top_k) from the program's trace-time counter ``moe.layer``,
summed over the window superstep's layers.  The program takes
``buffer_rows`` from the buffer shapes it compiles, less the row tile per
held expert that its layout may leave empty (``moe.pair_rows``), so this
is a check of the layer's structure, not a reading of the run:
100 means no skew of the routing can drop a pair, and a capacity-bounded
dispatch reads below it."""


def read(run):
    c = (run.counters or {}).get("moe.layer")
    if not c or c.get("pairs", 0) <= 0:
        return None
    return 100.0 * c["buffer_rows"] / c["pairs"]
