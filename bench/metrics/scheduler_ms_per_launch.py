"""Scheduler: device-idle self time of ``sched.admit``, ``sched.launch``
and ``sched.judge`` (admission, shedding, choice, judging in
``runtime/scheduler.py``) per launch, in ms (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.layer_ms_per_launch(ctx, "scheduler")
