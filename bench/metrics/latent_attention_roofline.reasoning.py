"""The latent-attention kernel's roofline time for the work the served
tokens need (live cached latents only), over its kernel time in the
trace, in %."""

from harness.metric_util import roofline


def read(run):
    return roofline(run, "latent_attention")
