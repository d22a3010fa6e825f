"""Share of the elements the fused GWT-Adam kernel updates whose gradient
and parameter it reads, and whose parameter it writes, in the leaves' own
buffers rather than through a copy (a stacked bucket, a transpose): the
program's trace-time counter ``gwt.kernel.in_place``, summed over the
window superstep's buckets.  A program without the counter reads
nothing.

The program sets the counter from how it calls the kernel (a transposed,
FIRST-orient leaf counts as copied), so this is a check of the update's
structure, not a reading of the run: it cannot see a copy or relayout
that the compiler puts around the launches.  The compiled step's HLO is
what shows those (``tests/test_tpu_compile.py``)."""


def read(run):
    c = (run.counters or {}).get("gwt.kernel.in_place")
    if not c:
        return None
    done, copied = c["elements_in_place"], c["elements_stacked"]
    if done + copied <= 0:
        return None
    return 100.0 * done / (done + copied)
