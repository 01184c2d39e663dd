"""Prompt tokens prefilled in the window over the token-rows its prefill
dispatches computed (bucket x max_slots each), from the engine's
``prefill_dispatch`` and ``prefill_chunk`` spans."""

from harness.metric_util import prefill_useful_share as read  # noqa: F401
