"""Dispatch: device-idle self time of ``serve.stack`` (the frame stack),
``serve.dispatch`` (eager per-block kernel dispatch and the
projection), ``serve.results`` (result slicing and the batch's
accounting) and ``serve.execute`` per launch, in ms
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.layer_ms_per_launch(ctx, "dispatch")
