"""Engine loop (core/engine.py): host milliseconds per step that the
device did not hide, from the delta of ``stats()`` over the window."""
from bench.readers import host_ms_per_step as read  # noqa: F401
