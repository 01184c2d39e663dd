"""The grouped expert kernel's roofline time for the work the served
tokens need (their top-k experts; the planes of the experts uniform
routing hits), over its kernel time in the trace, in %."""

from harness.metric_util import roofline


def read(run):
    return roofline(run, "expert_matmul")
