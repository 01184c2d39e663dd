"""Operations the served tokens need in the traced window over the
device's busy time at its bf16 peak, in %."""

from harness.metric_util import step_mfu as read  # noqa: F401
