"""1 - (union of device-op intervals / traced window)."""

from harness.metric_util import idle_share as read  # noqa: F401
