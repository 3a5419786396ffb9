"""Planner: device-idle self time of ``arbiter.split`` (the arbiter's
split and its grants adopted) and ``serve.plan`` (the specs-cache
lookup and ``core/plan.py`` ``replan``, cache hit or miss) per launch,
in ms (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.layer_ms_per_launch(ctx, "planner")
