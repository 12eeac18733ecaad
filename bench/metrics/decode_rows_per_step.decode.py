"""Scheduler (core/scheduler.py): decode rows per fused step, from the
delta of the runner's ``decode_tokens`` over ``ragged_steps``."""
from bench.readers import decode_rows_per_step as read  # noqa: F401
