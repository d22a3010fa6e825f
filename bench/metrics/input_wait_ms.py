"""Idle device time per training step under the train loop's input spans
(``train.input_wait``, ``train.place``, ``train.close``: pulling the
prefetched batches, stacking and placing them): the idle gaps whose middle
lies inside one of them (``bench/scopes.py``)."""


def read(run):
    if run.idle_under is None or not run.trace.chips:
        return None
    return run.idle_under["input"]
