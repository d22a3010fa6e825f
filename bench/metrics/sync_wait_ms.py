"""Idle device time per training step under the train loop's own
synchronisation spans (``train.block``, ``train.log``, ``train.dispatch``,
``train.dispatch_first``: the loss fetch, the log line, enqueueing a
superstep): the idle gaps whose middle lies inside one of them
(``bench/scopes.py``)."""


def read(run):
    if run.idle_under is None or not run.trace.chips:
        return None
    return run.idle_under["sync"]
