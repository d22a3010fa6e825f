"""The reader of ``gwt_norm_pass_share`` on hand-made counter samples of
``gwt.kernel.norm_pass``, as the training driver sums them into its
``RunInfo``."""

import pytest

from bench import harness
from bench.drivers.train import RunInfo, counter_sums
from bench.tests.tiny import BENCH

READ = harness.load_module(BENCH / "metrics" / "gwt_norm_pass_share.py").read


def _run(samples):
    events = [{"ph": "X", "name": "train.dispatch", "args": {}}] + [
        {"ph": "C", "name": "gwt.kernel.norm_pass",
         "args": {"elements_norm_only": only, "elements_full": full}}
        for only, full in samples]
    return RunInfo(arch=None, spec=None, traffic={}, chips=1, peaks=None,
                   tokens_per_s=0.0, steps=2, trace=None,
                   counters=counter_sums(events))


@pytest.mark.parametrize("samples, share", [
    ([(300.0, 0.0), (100.0, 0.0)], 100.0),     # every bucket norm-only
    ([(300.0, 0.0), (0.0, 100.0)], 75.0),      # one bucket on the q8 kernel
    ([(0.0, 0.0)], None)])                     # limiter off: nothing
def test_norm_pass_share_reads_the_counter(samples, share):
    got = READ(_run(samples))
    assert got == (None if share is None else pytest.approx(share))


def test_norm_pass_share_reads_nothing_without_the_counter():
    """A program without the counter (before the norm pass pinned its
    windows), or an untraced run, reads nothing."""
    assert READ(_run([])) is None
    bare = RunInfo(arch=None, spec=None, traffic={}, chips=1, peaks=None,
                   tokens_per_s=0.0, steps=2, trace=None)
    assert READ(bare) is None
