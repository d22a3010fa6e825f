"""The reader of ``gwt_in_place_share`` on hand-made counter samples of
``gwt.kernel.in_place``, as the training driver sums them into its
``RunInfo``."""

import pytest

from bench import harness
from bench.drivers.train import RunInfo, counter_sums
from bench.tests.tiny import BENCH

READ = harness.load_module(BENCH / "metrics" / "gwt_in_place_share.py").read


def _run(samples):
    events = [{"ph": "X", "name": "train.dispatch", "args": {}}] + [
        {"ph": "C", "name": "gwt.kernel.in_place",
         "args": {"elements_in_place": done, "elements_stacked": copied}}
        for done, copied in samples]
    return RunInfo(arch=None, spec=None, traffic={}, chips=1, peaks=None,
                   tokens_per_s=0.0, steps=2, trace=None,
                   counters=counter_sums(events))


@pytest.mark.parametrize("samples, share", [
    ([(300.0, 0.0), (100.0, 0.0)], 100.0),     # every bucket in place
    ([(300.0, 0.0), (0.0, 100.0)], 75.0),      # one bucket transposed
    ([(0.0, 0.0)], None)])                     # no elements: nothing
def test_in_place_share_reads_the_counter(samples, share):
    got = READ(_run(samples))
    assert got == (None if share is None else pytest.approx(share))


def test_in_place_share_reads_nothing_without_the_counter():
    """A program without the counter (before the leaves were updated
    where they live), or an untraced run, reads nothing."""
    assert READ(_run([])) is None
    bare = RunInfo(arch=None, spec=None, traffic={}, chips=1, peaks=None,
                   tokens_per_s=0.0, steps=2, trace=None)
    assert READ(bare) is None
