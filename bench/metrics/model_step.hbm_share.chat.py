"""Bytes the served tokens need in the traced window over the device's
busy time at its HBM bandwidth."""

from harness.metric_util import step_hbm_share as read  # noqa: F401
