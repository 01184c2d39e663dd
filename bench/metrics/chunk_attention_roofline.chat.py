"""Chunk attention's roofline time for the work the served tokens need
(live keys only), over its kernel time in the trace, in %."""

from harness.metric_util import roofline


def read(run):
    return roofline(run, "chunk_attention")
