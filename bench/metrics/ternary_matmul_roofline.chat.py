"""The ternary matmul's roofline time for the work the served tokens
need, over its kernel time in the trace, in %."""

from harness.metric_util import roofline


def read(run):
    return roofline(run, "ternary_matmul")
