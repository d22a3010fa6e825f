"""Share of the elements the fused GWT-Adam kernel updates that take its
one-pass lane shuffles (a bf16 gradient) rather than the three-pass ones:
the program's trace-time counter ``gwt.kernel.one_pass``, summed over the
window superstep's buckets."""


def read(run):
    c = (run.counters or {}).get("gwt.kernel.one_pass")
    if not c:
        return None
    one, three = c["elements_one_pass"], c["elements_three_pass"]
    if one + three <= 0:
        return None
    return 100.0 * one / (one + three)
